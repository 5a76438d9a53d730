"""Blocked causal attention with an online softmax: never more than one block
of scores alive, and only the key blocks a query block may see are visited.

:func:`blocked_attention` takes grouped queries ``[n, S, KV, R, hd]`` (R query
heads share a key-value head) and keys / values ``[n, S, KV, hd]``.  Query
``i`` sees key ``j`` when ``j <= i`` and, with ``window``, ``i - j < window``.
The sequence is cut into blocks of ``block`` positions (S is a multiple, the
caller pads; ``window`` is a multiple too).  Query block ``i`` visits the key
blocks ``max(i - window / block, 0) .. i`` (:func:`key_block_range`): a window
layer the diagonal block and the ones its window reaches, a full layer the
lower triangle.  The blocks the mask rules out are not computed and masked:
the grid's key axis is only as long as the longest visit, a step past a
query block's visit does nothing and fetches nothing (its block index stands
still), and only the first and last visited blocks apply a mask inside.
:func:`visited_key_blocks` is that schedule's count, a number the code holds.

Three Pallas TPU kernels (``interpret=`` runs them on the CPU, as
``ops/hist_pallas.py``'s): the forward keeps a running row maximum, row sum
and output in VMEM and writes the output and the rows' log-sum-exp; the
backward (``custom_vjp``) recomputes each block's probabilities from the saved
log-sum-exp — one kernel walks a query block's key blocks for ``dq``, one
walks a key block's query blocks, over the R query heads that share it, for
``dk`` and ``dv`` with the scores transposed, so that the row statistics
broadcast along lanes.  Heads are addressed in place: the arrays stay ``[n, S,
heads x hd]`` and a block is ``[block, hd]`` at the head's lane offset, so
nothing is transposed on the way in or out (on the chip ``hd`` is a multiple
of 128).

Precision: f32 in and out.  The MXU's operands (q, k, v, the probabilities,
the output's cotangent) are rounded once to :func:`..ops.moe.mxu_operand_dtype`
(bfloat16 on the TPU), every product accumulates in f32, scores, softmax and
the running statistics are f32.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .moe import mxu_operand_dtype

BLOCK = 512                     # positions of a query / key block on the chip
_MASK = -0.7 * float(jnp.finfo(jnp.float32).max)       # a ruled-out score
_NT = (((1,), (1,)), ((), ()))  # [a, d] x [b, d] -> [a, b]


def key_block_range(i, window_blocks, maximum=jnp.maximum):
    """(first, last) key block that query block ``i`` visits; ``window_blocks``
    = window / block, or None for a full causal layer."""
    return (0 if window_blocks is None else maximum(i - window_blocks, 0)), i


def visited_key_blocks(seq: int, block: int = BLOCK, window=None) -> int:
    """Key blocks one head's forward visits over a sequence of ``seq``."""
    wb = _window_blocks(seq, block, window)
    total = 0
    for i in range(seq // block):
        lo, hi = key_block_range(i, wb, max)
        total += hi - lo + 1
    return total


def _window_blocks(seq: int, block: int, window):
    if seq % block:
        raise ValueError(f"a sequence of {seq} positions is not whole blocks of {block}")
    if window is None or window >= seq:
        return None
    if window % block:
        raise ValueError(f"a window of {window} keys is not whole blocks of {block}")
    return window // block


def _allowed(i, j, block, wb, transposed=False):
    """[block, block] bool: which (query, key) pairs of query block ``i`` and
    key block ``j`` the mask allows (keys x queries when ``transposed``)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    qp, kp = (cols, rows) if transposed else (rows, cols)
    back = (i - j) * block + qp - kp                       # keys back from the query
    ok = back >= 0
    return ok if wb is None else ok & (back < wb * block)


def _needs_mask(i, j, wb):
    return (j == i) if wb is None else (j == i) | (j == i - wb)


def _visit_if(active, needs_mask, visit):
    """Run ``visit(masked)`` when the step is inside the visit: the masked
    variant on the blocks the mask cuts, the plain one elsewhere."""
    pl.when(active & needs_mask)(lambda: visit(True))
    pl.when(active & jnp.logical_not(needs_mask))(lambda: visit(False))


def _kv_index(wb, r):
    """Index map of a key / value block for grid point (sequence, query head,
    query block, step): the step's key block, standing still past the visit."""
    def at(b, h, i, t):
        lo, hi = key_block_range(i, wb)
        return b, jnp.minimum(lo + t, hi), h // r
    return at


def _lanes(x, width):
    """[rows, lanes] (every lane alike) -> [rows, width]."""
    reps = -(-width // x.shape[1])
    return x if width == x.shape[1] else jnp.tile(x, (1, reps))[:, :width]


# ------------------------------------------------------------------ forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *, block, wb, steps):
    i, t = pl.program_id(2), pl.program_id(3)
    lo, hi = key_block_range(i, wb)
    j = lo + t

    @pl.when(t == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, _MASK)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def visit(masked):
        s = jax.lax.dot_general(q_ref[...], k_ref[...], _NT, preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_allowed(i, j, block, wb), s, _MASK)
        m_prev = m_s[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, block))
        alpha = jnp.exp(m_prev - m_next)
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=-1, keepdims=True)
        m_s[...] = m_next
        acc_s[...] = _lanes(alpha, acc_s.shape[1]) * acc_s[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...], preferred_element_type=jnp.float32)

    _visit_if(j <= hi, _needs_mask(i, j, wb), visit)

    @pl.when(t == steps - 1)
    def _():
        l = l_s[...]
        o_ref[...] = acc_s[...] * _lanes(1.0 / l, acc_s.shape[1])
        lse_ref[...] = m_s[...] + jnp.log(l)


def _grid_steps(nq: int, wb) -> int:
    """The key axis of the grid: the longest visit of any query block."""
    return nq if wb is None else min(wb + 1, nq)


def _forward(q, k, v, wb, block, heads, interpret):
    n, seq, width = q.shape
    hd = width // heads
    r = heads // (k.shape[2] // hd)
    nq, lanes = seq // block, min(128, block)
    steps = _grid_steps(nq, wb)
    kv_at, at_q = _kv_index(wb, r), lambda b, h, i, t: (b, i, h)
    o, lse = pl.pallas_call(
        partial(_fwd_kernel, block=block, wb=wb, steps=steps),
        grid=(n, heads, nq, steps),
        in_specs=[pl.BlockSpec((None, block, hd), at_q),
                  pl.BlockSpec((None, block, hd), kv_at),
                  pl.BlockSpec((None, block, hd), kv_at)],
        out_specs=[pl.BlockSpec((None, block, hd), at_q),
                   pl.BlockSpec((None, None, block, lanes), lambda b, h, i, t: (b, h, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, seq, width), jnp.float32),
                   jax.ShapeDtypeStruct((n, heads, seq, lanes), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, lanes), jnp.float32),
                        pltpu.VMEM((block, lanes), jnp.float32),
                        pltpu.VMEM((block, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        name="blocked_attention_fwd", interpret=interpret,
    )(q, k, v)
    return o, lse[..., 0]


# ----------------------------------------------------------------- backward
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_s, *, block, wb, steps):
    i, t = pl.program_id(2), pl.program_id(3)
    lo, hi = key_block_range(i, wb)
    j = lo + t

    @pl.when(t == 0)
    def _():
        acc_s[...] = jnp.zeros_like(acc_s)

    def visit(masked):
        s = jax.lax.dot_general(q_ref[...], k_ref[...], _NT, preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_allowed(i, j, block, wb), s, _MASK)
        p = jnp.exp(s - jnp.expand_dims(lse_ref[0], -1))
        dp = jax.lax.dot_general(do_ref[...], v_ref[...], _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.expand_dims(delta_ref[0], -1))
        acc_s[...] += jnp.dot(ds.astype(k_ref.dtype), k_ref[...], preferred_element_type=jnp.float32)

    _visit_if(j <= hi, _needs_mask(i, j, wb), visit)

    @pl.when(t == steps - 1)
    def _():
        dq_ref[...] = acc_s[...]


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_s, dv_s, *,
                block, wb, steps, nq, r):
    j, t = pl.program_id(2), pl.program_id(3)
    i = j + t % steps                                       # the query block, of head t // steps

    @pl.when(t == 0)
    def _():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def visit(masked):
        s = jax.lax.dot_general(k_ref[...], q_ref[...], _NT, preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_allowed(i, j, block, wb, transposed=True), s, _MASK)
        p = jnp.exp(s - lse_ref[...])                       # [keys, queries] - [1, queries]
        dv_s[...] += jnp.dot(p.astype(do_ref.dtype), do_ref[...], preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[...], do_ref[...], _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[...])
        dk_s[...] += jnp.dot(ds.astype(q_ref.dtype), q_ref[...], preferred_element_type=jnp.float32)

    _visit_if(i < nq, _needs_mask(i, j, wb), visit)

    @pl.when(t == r * steps - 1)
    def _():
        dk_ref[...] = dk_s[...]
        dv_ref[...] = dv_s[...]


def _backward(q, k, v, o, lse, do, wb, block, heads, interpret):
    n, seq, width = q.shape
    hd = width // heads
    kv = k.shape[2] // hd
    r = heads // kv
    nq = seq // block
    steps = _grid_steps(nq, wb)
    delta = jnp.sum((do * o).reshape(n, seq, heads, hd), axis=-1).transpose(0, 2, 1)[:, :, None]
    lse, do = lse[:, :, None], do.astype(q.dtype)           # [n, heads, 1, S]: a row a block

    kv_at, at_q = _kv_index(wb, r), lambda b, h, i, t: (b, i, h)
    row_q = lambda b, h, i, t: (b, h, 0, i)
    dq = pl.pallas_call(
        partial(_dq_kernel, block=block, wb=wb, steps=steps),
        grid=(n, heads, nq, steps),
        in_specs=[pl.BlockSpec((None, block, hd), at_q),
                  pl.BlockSpec((None, block, hd), kv_at),
                  pl.BlockSpec((None, block, hd), kv_at),
                  pl.BlockSpec((None, block, hd), at_q),
                  pl.BlockSpec((None, None, 1, block), row_q),
                  pl.BlockSpec((None, None, 1, block), row_q)],
        out_specs=pl.BlockSpec((None, block, hd), at_q),
        out_shape=jax.ShapeDtypeStruct((n, seq, width), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        name="blocked_attention_dq", interpret=interpret,
    )(q, k, v, do, lse, delta)

    # a key block's query blocks: j .. j + steps - 1 (those past the sequence
    # do nothing), over the r query heads that share the key-value head
    q_block = lambda j, t: jnp.minimum(j + t % steps, nq - 1)
    q_of = lambda b, g, j, t: (b, q_block(j, t), g * r + t // steps)
    row_of = lambda b, g, j, t: (b, g * r + t // steps, 0, q_block(j, t))
    at_kv = lambda b, g, j, t: (b, j, g)
    dk, dv = pl.pallas_call(
        partial(_dkv_kernel, block=block, wb=wb, steps=steps, nq=nq, r=r),
        grid=(n, kv, nq, r * steps),
        in_specs=[pl.BlockSpec((None, block, hd), q_of),
                  pl.BlockSpec((None, block, hd), at_kv),
                  pl.BlockSpec((None, block, hd), at_kv),
                  pl.BlockSpec((None, block, hd), q_of),
                  pl.BlockSpec((None, None, 1, block), row_of),
                  pl.BlockSpec((None, None, 1, block), row_of)],
        out_specs=[pl.BlockSpec((None, block, hd), at_kv)] * 2,
        out_shape=[jax.ShapeDtypeStruct(k.shape, jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((block, hd), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        name="blocked_attention_dkv", interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attend(q, k, v, wb, block, heads, interpret):
    return _attend_fwd(q, k, v, wb, block, heads, interpret)[0]


def _attend_fwd(q, k, v, wb, block, heads, interpret):
    dt = mxu_operand_dtype(q)
    q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)      # once, not a block
    o, lse = _forward(q, k, v, wb, block, heads, interpret)
    return o, (q, k, v, o, lse)


def _attend_bwd(wb, block, heads, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _backward(q, k, v, o, lse, do, wb, block, heads, interpret)
    return dq.astype(do.dtype), dk.astype(do.dtype), dv.astype(do.dtype)


_attend.defvjp(_attend_fwd, _attend_bwd)


def blocked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, window=None,
                      block: int = BLOCK, interpret=None) -> jnp.ndarray:
    """Causal grouped-query attention, ``window`` keys back when given.

    q [n, S, KV, R, hd], k / v [n, S, KV, hd], f32 -> [n, S, KV, R, hd] f32:
    ``softmax_j(q_i . k_j / sqrt(hd)) v_j`` over the keys ``j <= i`` (and ``i -
    j < window``).  S is a multiple of ``block``, and so is a ``window``
    shorter than S (a longer one is a full layer).  ``interpret`` None: the
    Pallas interpreter anywhere but on a TPU."""
    n, seq, kv, r, hd = q.shape
    wb = _window_blocks(seq, block, window)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    o = _attend((q * (1.0 / math.sqrt(hd))).reshape(n, seq, kv * r * hd),
                k.reshape(n, seq, kv * hd), v.reshape(n, seq, kv * hd),
                wb, block, kv * r, bool(interpret))
    return o.reshape(n, seq, kv, r, hd)
