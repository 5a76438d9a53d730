"""Mixture-of-experts routing for one rank's share of the experts.

The rank is told which experts it holds (``lo .. lo + held``), routes every
token over ALL experts (:func:`route`: a softmax router, or sigmoid scores
with a selection bias; top-k, renormalised), and computes its own experts'
part of the result (:func:`held_experts_ffn`: SwiGLU experts, or two matrices
around relu^2 — one sort, one set of grouped products, one backward): the (token, choice) pairs whose expert is
held are sorted by expert, pushed through grouped matrix products
(``jax.lax.ragged_dot``: one row group an expert, the TPU's grouped-matmul
kernel, which skips the rows past the groups) and gathered back to their
tokens with their router weights.  What the absent experts would add is left
out — there is no stand-in for the other ranks or their exchange.

No pair is ever dropped: the pair buffer has one slot for every (token,
choice) that can be routed here — a token's choices are distinct experts, so
at most ``min(k, held)`` of them — so the worst imbalance — every token on one
expert, every choice held — still fits.  ``dropped`` in the counters is measured pair by pair
(:func:`covered_pairs`): a pair counts as covered when the buffer row it is
read back from lies inside the row group of its own expert, which is what a
tighter buffer or clipped group sizes would break.

Precision: matmul operands are rounded once to :func:`mxu_operand_dtype`
(bfloat16 on the TPU — what its default precision does to f32 operands anyway,
at half the bytes through the gathers; the input's own dtype elsewhere), every product
accumulates and leaves in f32, forward and backward; the backward is written
out (``custom_vjp``) because autodiff would hand the cotangents back in the
operands' dtype.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.lax import RaggedDotDimensionNumbers

# [P, K] x [P, N] -> [G, K, N]: the pair axis is contracted group by group
_CONTRACT_PAIRS = RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def mxu_operand_dtype(like):
    """bfloat16 on the TPU, else the array's own dtype."""
    return jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.asarray(like).dtype


def route(x: jnp.ndarray, w_router: jnp.ndarray, top_k: int, norm_topk: bool = True,
          bias: Optional[jnp.ndarray] = None, scale: float = 1.0
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [T, D] -> (weights [T, k] f32, experts [T, k] int32) over ALL the
    router's experts.  Router matmul, scores and renormalisation in f32.
    Without ``bias``: softmax scores, the top-k of them.  With ``bias`` [E]
    (the selection bias; zeros count): sigmoid scores, chosen = the top-k of
    score + bias, weights = the chosen *scores*; the bias takes no gradient.
    ``scale`` multiplies the weights after the renormalisation."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if bias is None:
        top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    else:
        scores = jax.nn.sigmoid(logits)
        _, top_e = jax.lax.top_k(scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
        top_p = jnp.take_along_axis(scores, top_e, axis=-1)
    if norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if scale != 1.0:
        top_p = top_p * scale
    return top_p, top_e.astype(jnp.int32)


def _grouped(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=jnp.float32)


def _grouped_outer(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot_general(lhs, rhs, group_sizes, _CONTRACT_PAIRS,
                                      preferred_element_type=jnp.float32)


def _swiglu(gu):
    f = gu.shape[-1] // 2
    return jax.nn.silu(gu[:, :f]) * gu[:, f:]


def _swiglu_bwd(gu, dh):
    f = gu.shape[-1] // 2
    g, u = gu[:, :f], gu[:, f:]
    sg = jax.nn.sigmoid(g)
    return jnp.concatenate([dh * u * sg * (1.0 + g * (1.0 - sg)), dh * g * sg], axis=1)


def _relu2(a):
    return jnp.square(jax.nn.relu(a))


def _relu2_bwd(a, dh):
    return dh * 2.0 * jax.nn.relu(a)


# the expert's body between its two matrices: the first product's output
# [P, 2F] (gate then up) or [P, F] -> [P, F], and its backward
ACTS = {"swiglu": (_swiglu, _swiglu_bwd), "relu2": (_relu2, _relu2_bwd)}


def _gather_pairs(rows, slot, is_held):
    """rows [P, D] in sorted-pair order -> [T, k, D] by (token, choice); the
    slots of absent experts (rows past the groups: whatever the kernel left)
    read as 0."""
    t, k = is_held.shape
    return jnp.where(is_held[..., None], rows[slot].reshape(t, k, -1), 0.0)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ffn(k, dt, act, x, weights, w_gate_up, w_down, group_sizes, order, slot, is_held):
    return _ffn_fwd(k, dt, act, x, weights, w_gate_up, w_down, group_sizes, order, slot, is_held)[0]


def _ffn_fwd(k, dt, act, x, weights, w_gate_up, w_down, group_sizes, order, slot, is_held):
    xs = x.astype(dt)[order // k]                                   # [P, D]
    gu = _grouped(xs, w_gate_up.astype(dt), group_sizes)            # [P, 2F] (or [P, F]) f32
    ys = _grouped(ACTS[act][0](gu).astype(dt), w_down.astype(dt), group_sizes)   # [P, D] f32
    y_pairs = _gather_pairs(ys, slot, is_held)                      # [T, k, D]
    y = jnp.sum(y_pairs * weights[..., None], axis=1)
    return y, (xs, gu, y_pairs, weights, w_gate_up, w_down, group_sizes, order, slot, is_held)


def _ffn_bwd(k, dt, act, res, dy):
    xs, gu, y_pairs, weights, w_gate_up, w_down, group_sizes, order, slot, is_held = res
    d_weights = jnp.sum(y_pairs * dy[:, None, :], axis=-1)
    w_sorted = weights.reshape(-1)[order][:, None]                  # this pair's weight
    dys = dy.astype(dt)[order // k]                                 # [P, D]; the weight goes on after
    h = ACTS[act][0](gu)
    d_w_down = _grouped_outer((h * w_sorted).astype(dt), dys, group_sizes)
    dh = w_sorted * _grouped(dys, jnp.swapaxes(w_down, 1, 2).astype(dt), group_sizes)
    dgu = ACTS[act][1](gu, dh).astype(dt)
    d_w_gate_up = _grouped_outer(xs, dgu, group_sizes)
    dxs = _grouped(dgu, jnp.swapaxes(w_gate_up, 1, 2).astype(dt), group_sizes)
    dx = jnp.sum(_gather_pairs(dxs, slot, is_held), axis=1)
    return (dx.astype(dy.dtype), d_weights, d_w_gate_up.astype(w_gate_up.dtype),
            d_w_down.astype(w_down.dtype), None, None, None, None)


_ffn.defvjp(_ffn_fwd, _ffn_bwd)


def covered_pairs(slot, local, is_held, group_sizes) -> jnp.ndarray:
    """How many of the pairs routed to a held expert the grouped products
    really computed with that expert's weights: the pair's buffer row
    (``slot``, from the sort) must lie inside its expert's row group (from
    ``group_sizes``, which is all the kernel sees).  slot/local/is_held
    [T, k]; group_sizes [held]."""
    ends = jnp.cumsum(group_sizes)
    e = jnp.clip(local, 0, group_sizes.shape[0] - 1)
    inside = (slot >= (ends - group_sizes)[e]) & (slot < ends[e])
    return jnp.sum(is_held & inside)


def held_experts_ffn(x: jnp.ndarray, weights: jnp.ndarray, experts: jnp.ndarray,
                     w_gate_up: jnp.ndarray, w_down: jnp.ndarray, lo: int,
                     act: str = "swiglu") -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The held experts' part of the MoE output.

    x [T, D] f32; weights/experts [T, k] from :func:`route`; ``w_gate_up``
    [held, D, 2F] (gate then up; [held, D, F] under ``act="relu2"``: one
    matrix, no gate), ``w_down`` [held, F, D]; the rank holds experts ``lo ..
    lo + held``.  Returns (y [T, D] f32, counters): ``pairs`` [held] pairs
    per held expert, ``dropped`` pairs routed here less :func:`covered_pairs`."""
    n_tok, k = experts.shape
    held = w_gate_up.shape[0]
    dt = mxu_operand_dtype(x)
    local = experts - lo
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held).reshape(-1)         # absent experts sort last
    order = jnp.argsort(key, stable=True)                     # pair ids, by expert
    if held < k:
        # a token's choices are distinct: at most ``held`` of its k are routed
        # here, and they sort first, so the buffer needs no more rows
        order = order[:n_tok * held]
    slot = jnp.zeros(n_tok * k, order.dtype).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype)).reshape(n_tok, k)
    # absent experts' pairs all read row 0 (and are masked): the gathers back
    # to (token, choice) then touch only the rows the groups filled
    slot = jnp.where(is_held, slot, 0)
    group_sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    y = _ffn(k, dt, act, x, jnp.where(is_held, weights, 0.0), w_gate_up, w_down, group_sizes,
             order, slot, is_held)
    counters = {"pairs": group_sizes,
                "dropped": (jnp.sum(is_held) - covered_pairs(slot, local, is_held, group_sizes)
                            ).astype(jnp.int32)}
    return y, counters
