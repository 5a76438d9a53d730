"""Mixture-of-experts routing for one rank's share of the experts.

The rank is told which experts it holds (``lo .. lo + held``), routes every
token over ALL experts (:func:`route`: a softmax router, or sigmoid scores
with a selection bias; top-k, renormalised; :func:`route_scores` hands out the
scores too, for a balance loss), and computes its own experts'
part of the result (:func:`held_experts_ffn`: SwiGLU experts, or two matrices
around relu^2 — one sort, one walk, one backward).  What the absent experts
would add is left out — there is no stand-in for the other ranks or their
exchange.

The walk.  The (token, choice) pairs are sorted by expert, the absent
experts' last, and the sorted list is walked in chunks of T rows (T = the
token count, rounded up to :data:`ROW_TILE`): a chunk gathers its pairs' tokens, pushes them through grouped
matrix products (``jax.lax.ragged_dot``: one row group an expert, the TPU's
grouped-matmul kernel) with the group sizes cut to the chunk, weighs each row
with its router weight and scatter-adds it onto its token.  The trip count is
``ceil(pairs routed here / T)``, read on the device from the group sizes: the
work follows the pairs the router sent, not the worst case, and only ``[T, k]``
scalars ever exist in (token, choice) layout.  The backward is the same walk
(``custom_vjp``: autodiff would hand the cotangents back in the operands'
dtype, and cannot transpose a loop whose length is a device value); weight
gradients add up in the loop's carry.  ``rows`` in the counters is chunks run
x their rows: pairs over rows is how full the walk's chunks were.

No pair is ever dropped and there is no capacity: a token's choices are
distinct experts, so at most ``min(k, held)`` of them are routed here, and the
sorted list has ``min(k, held)`` chunks — the worst imbalance, every token on
one expert and every choice held, runs them all.  ``dropped`` in the counters
is measured pair by pair (:func:`covered_pairs`): a pair counts as covered
when the expert whose row group its buffer row lies in — by its chunk's group
sizes, which is all the kernel sees — is the expert the router chose, which is
what a tighter buffer or clipped group sizes would break.

Precision: matmul operands are rounded once to :func:`mxu_operand_dtype`
(bfloat16 on the TPU — what its default precision does to f32 operands anyway,
at half the bytes through the gathers; the input's own dtype elsewhere), every
product accumulates and leaves in f32, forward and backward, and the router
weights and the sums over a token's pairs are f32.
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


# a chunk's rows are the token count rounded up to this: the grouped-matmul
# kernel tiles its rows by the largest power of two that divides them, and at
# 3,448 rows (8 x 431: nemotron_h's MTP layer) one product took 6.4 ms where
# 3,456 rows take 0.5 (PERF.md section 6, PR 32)
ROW_TILE = 128


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
    return route_scores(x, w_router, top_k, norm_topk, bias, scale)[:2]


def route_scores(x: jnp.ndarray, w_router: jnp.ndarray, top_k: int, norm_topk: bool = True,
                 bias: Optional[jnp.ndarray] = None, scale: float = 1.0
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """:func:`route`'s (weights, experts) and the scores [T, E] f32 it chose
    from (softmax, or sigmoid without the bias), from the same router product:
    what a balance loss over all experts reads."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if bias is None:
        scores = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(scores, top_k)
    else:
        scores = jax.nn.sigmoid(logits)
        _, top_e = jax.lax.top_k(scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
        top_p = jnp.take_along_axis(scores, top_e, axis=-1)
    if norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if scale != 1.0:
        top_p = top_p * scale
    return top_p, top_e.astype(jnp.int32), scores


def bias_step(bias: jnp.ndarray, tokens: jnp.ndarray, coeff: float) -> jnp.ndarray:
    """The selection bias after an optimizer step whose top-k counts were
    ``tokens`` [E] (every expert, held or not): ``b + coeff x sign(mean(n) -
    n_e)`` — DeepSeek-V3's auxiliary-loss-free rule (arXiv:2412.19437), not
    centred.  No gradient reaches ``b``; this is all that moves it."""
    return bias + coeff * jnp.sign(jnp.mean(tokens) - tokens)


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


def chunk_sizes(group_sizes: jnp.ndarray, n_chunks: int, rows: int) -> jnp.ndarray:
    """[n_chunks, held]: how many rows of each expert's group lie in each
    chunk of ``rows`` buffer rows — the group sizes a chunk's grouped products
    are given.  Chunks past the groups read all 0."""
    ends = jnp.cumsum(group_sizes)
    r0 = (jnp.arange(n_chunks, dtype=ends.dtype) * rows)[:, None]
    return (jnp.clip(ends - r0, 0, rows)
            - jnp.clip(ends - group_sizes - r0, 0, rows)).astype(jnp.int32)


def _chunks_run(sizes, rows):
    """Chunks that hold a routed pair: the walks' trip count, a device value."""
    return (jnp.sum(sizes) + rows - 1) // rows


def _chunk(c, k, sizes, order, w_flat):
    """Chunk ``c`` of the sorted pair list: (first row, pair ids [C], their
    tokens, the chunk's group sizes, which rows a group filled, the rows'
    router weights with the unfilled rows' at 0)."""
    n_rows = order.shape[0] // sizes.shape[0]
    r0 = c * n_rows
    rows = jax.lax.dynamic_slice(order, (r0,), (n_rows,))
    gs = sizes[c]
    filled = jnp.arange(n_rows) < jnp.sum(gs)
    return r0, rows, rows // k, gs, filled, jnp.where(filled, w_flat[rows], 0.0)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ffn(k, dt, act, x, w_flat, w_gate_up, w_down, sizes, order):
    return _ffn_fwd(k, dt, act, x, w_flat, w_gate_up, w_down, sizes, order)[0]


def _ffn_fwd(k, dt, act, x, w_flat, w_gate_up, w_down, sizes, order):
    n_rows = order.shape[0] // sizes.shape[0]                       # of a chunk
    xd, wgu, wd = x.astype(dt), w_gate_up.astype(dt), w_down.astype(dt)   # once, not a chunk

    def body(c, carry):
        y, gu_all = carry
        r0, _, tok, gs, filled, wr = _chunk(c, k, sizes, order, w_flat)
        gu = _grouped(xd[tok], wgu, gs)                             # [C, 2F] (or [C, F]) f32
        ys = _grouped(ACTS[act][0](gu).astype(dt), wd, gs)          # [C, D] f32
        # rows past the groups hold whatever the kernel left: they add 0
        y = y.at[tok].add(jnp.where(filled[:, None], ys, 0.0) * wr[:, None])
        return y, jax.lax.dynamic_update_slice(gu_all, gu, (r0, 0))

    y, gu_all = jax.lax.fori_loop(
        0, _chunks_run(sizes, n_rows), body,
        (jnp.zeros(x.shape, jnp.float32),
         jnp.zeros((order.shape[0], w_gate_up.shape[2]), jnp.float32)))
    return y, (x, gu_all, w_flat, w_gate_up, w_down, sizes, order)


def _ffn_bwd(k, dt, act, res, dy):
    x, gu_all, w_flat, w_gate_up, w_down, sizes, order = res
    n_rows = order.shape[0] // sizes.shape[0]
    xd, dyd = x.astype(dt), dy.astype(dt)
    wd_t, wgu_t = jnp.swapaxes(w_down, 1, 2).astype(dt), jnp.swapaxes(w_gate_up, 1, 2).astype(dt)

    def body(c, carry):
        dx, d_w, d_wgu, d_wd = carry
        r0, rows, tok, gs, filled, wr = _chunk(c, k, sizes, order, w_flat)
        wr = wr[:, None]
        gu = jax.lax.dynamic_slice(gu_all, (r0, 0), (n_rows, gu_all.shape[1]))
        h = ACTS[act][0](gu)
        dys = dyd[tok]                                              # the weight goes on after
        dh = _grouped(dys, wd_t, gs)                                # [C, F] f32
        # the pair's output . dy, as h . (W_down dy): no third product, no f32 rows of dy
        d_w = d_w.at[rows].set(jnp.where(filled, jnp.sum(h * dh, axis=-1), 0.0),
                               unique_indices=True)
        d_wd = d_wd + _grouped_outer((h * wr).astype(dt), dys, gs)
        dgu = ACTS[act][1](gu, wr * dh).astype(dt)
        d_wgu = d_wgu + _grouped_outer(xd[tok], dgu, gs)
        dxs = _grouped(dgu, wgu_t, gs)
        return dx.at[tok].add(jnp.where(filled[:, None], dxs, 0.0)), d_w, d_wgu, d_wd

    f32 = lambda like: jnp.zeros(like.shape, jnp.float32)
    dx, d_w, d_wgu, d_wd = jax.lax.fori_loop(
        0, _chunks_run(sizes, n_rows), body, (f32(x), f32(w_flat), f32(w_gate_up), f32(w_down)))
    return (dx.astype(dy.dtype), d_w, d_wgu.astype(w_gate_up.dtype), d_wd.astype(w_down.dtype),
            None, None)


_ffn.defvjp(_ffn_fwd, _ffn_bwd)


def covered_pairs(chosen, sizes) -> jnp.ndarray:
    """How many of the pairs routed to a held expert the grouped products
    really compute with that expert's weights: the expert whose group the
    pair's buffer row lies in — by its chunk's group sizes, which is all the
    kernel sees — must be the expert the router chose.  chosen [P]: the
    chosen expert of the pair in each buffer row, from the first held one
    (an absent expert: any value outside ``0 .. held``); sizes [n_chunks,
    held] (:func:`chunk_sizes`)."""
    n_chunks, held = sizes.shape
    at = jnp.arange(chosen.shape[0] // n_chunks)
    # groups that end at or before the row; held = past the chunk's groups: no pair's expert
    lands_in = jnp.sum(at[None, :, None] >= jnp.cumsum(sizes, axis=1)[:, None, :], axis=-1)
    return jnp.sum((lands_in < held) & (lands_in == chosen.reshape(n_chunks, -1)))


def held_experts_ffn(x: jnp.ndarray, weights: jnp.ndarray, experts: jnp.ndarray,
                     w_gate_up: jnp.ndarray, w_down: jnp.ndarray, lo: int,
                     act: str = "swiglu") -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The held experts' part of the MoE output.

    x [T, D] f32; weights/experts [T, k] from :func:`route`; ``w_gate_up``
    [held, D, 2F] (gate then up; [held, D, F] under ``act="relu2"``: one
    matrix, no gate), ``w_down`` [held, F, D]; the rank holds experts ``lo ..
    lo + held``.  Returns (y [T, D] f32, counters): ``pairs`` [held] pairs
    per held expert, ``rows`` buffer rows the walk computed (chunks run x
    a chunk's rows), ``dropped`` pairs routed here less :func:`covered_pairs`."""
    n_tok, k = experts.shape
    held = w_gate_up.shape[0]
    n_chunks = min(k, held)
    n_rows = -(-n_tok // ROW_TILE) * ROW_TILE                 # of a chunk
    dt = mxu_operand_dtype(x)
    local = experts - lo
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held).reshape(-1)         # absent experts sort last
    # the pairs by expert.  A token's choices are distinct: at most ``held`` of
    # its k are routed here, and they sort first, so the buffer needs no more rows
    chosen, order = jax.lax.sort_key_val(key, jnp.arange(key.shape[0], dtype=jnp.int32))
    # rows added by the rounding belong to no pair: ids past the last one, each
    # its own, which a gather clamps and a scatter leaves out
    pad = n_chunks * (n_rows - n_tok)
    chosen = jnp.pad(chosen[:n_chunks * n_tok], (0, pad), constant_values=held)
    order = jnp.concatenate([order[:n_chunks * n_tok],
                             key.shape[0] + jnp.arange(pad, dtype=order.dtype)])
    group_sizes = jnp.diff(jnp.searchsorted(chosen, jnp.arange(held + 1))).astype(jnp.int32)
    sizes = chunk_sizes(group_sizes, n_chunks, n_rows)
    y = _ffn(k, dt, act, x, jnp.where(is_held, weights, 0.0).reshape(-1), w_gate_up, w_down,
             sizes, order)
    counters = {"pairs": group_sizes,
                "rows": (_chunks_run(sizes, n_rows) * n_rows).astype(jnp.int32),
                "dropped": (jnp.sum(is_held) - covered_pairs(chosen, sizes)).astype(jnp.int32)}
    return y, counters
