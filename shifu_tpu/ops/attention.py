"""Blocked attention with an online softmax: never more than one block of
scores alive, and only the key blocks the mask allows are visited.

:func:`blocked_attention` takes grouped queries ``[n, S, KV, R, hd]`` (R query
heads share a key-value head), keys ``[n, S, KV, hd]`` and values ``[n, S, KV,
dv]`` of a width of their own (latent attention scores over 192 channels and
carries 128; ``dv == hd`` elsewhere).  **The mask
is data**: a :class:`Mask` gives every query position the keys it may see as
at most two half-open intervals ``[lo0, hi0) u [lo1, hi1)`` of key positions —
causal ``[0, i + 1)``, a window ``[max(i - w + 1, 0), i + 1)``
(:func:`causal_mask`), or what :func:`mask_of` reads off a dense ``[S, S]``
statement of any other mask (block-causal, block diffusion over ``[x_t ;
x_0]``, padded halves whose pad keys lie in nobody's interval).  A mask that
needs a third interval is refused, never approximated; a query with no key
gets a zero output, and its cotangent reaches nothing.  No kernel code knows a
model or a kind of mask.

The sequence is cut into blocks of ``block`` positions (S is a multiple, the
caller pads).  :func:`schedule` derives, in NumPy at trace time, each query
block's list of key blocks that hold an allowed pair, whether a visited block
pair is wholly allowed (no mask applied inside) or cut, and then by which of
the four bounds (the predicate is evaluated from those alone; the kernels read
the bounds as an operand: a ``[block, lanes]`` plane a bound, every lane alike,
where queries are rows, and a ``[4, block]`` block where they are columns),
and the transposed lists for ``dk`` / ``dv``.  The lists reach the
index maps as scalar-prefetch tables: the grid's key axis is only as long as
the longest visit, and a step past a query block's visit does nothing and
fetches nothing (its block index stands still).  :func:`visited_key_blocks`
is that schedule's count, a number the code holds.

Three Pallas TPU kernels (``interpret=`` runs them on the CPU, as
``ops/hist_pallas.py``'s): the forward keeps a running row maximum, row sum
and output in VMEM and writes the output and the rows' log-sum-exp; the
backward (``custom_vjp``) recomputes each block's probabilities from the saved
log-sum-exp — one kernel walks a query block's key blocks for ``dq``, one
walks a key block's query blocks, over the R query heads that share it, for
``dk`` and ``dv`` with the scores transposed, so that the row statistics
broadcast along lanes.  Heads are addressed in place: the arrays stay ``[n, S,
heads x hd]`` (v, the output and its cotangent ``[n, S, heads x dv]``) and a
block is ``[block, hd]`` (``[block, dv]``) at the head's lane offset, so
nothing is transposed on the way in or out.  Keys and values of one width go
in as one stacked ``[n, 2, S, KV x hd]`` operand, a block pair fetched as one;
of two widths as two operands, ``dk`` and ``dv`` written at their own widths.
Mosaic takes a block only when its width is whole lanes (:data:`LANES`): on
the chip a narrower q / k head (``lfm2_moe``'s 64, latent attention's 192) is
padded with zero channels on the way in, which add nothing to a score, and v
is padded to its own whole lanes alone (128 stays 128); the output's zero
channels are cut off on the way out.  A caller that builds q and k at
:func:`lane_width` hands them over with ``qk_dim``, and nothing pads them again.

Precision: f32 in and out.  The MXU's operands (q, k, v, the probabilities,
the output's cotangent) are rounded once to :func:`..ops.moe.mxu_operand_dtype`
(bfloat16 on the TPU), every product accumulates in f32, scores, softmax and
the running statistics are f32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import lru_cache, partial, reduce

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .moe import mxu_operand_dtype

BLOCK = 512                     # positions of a query / key block on the chip
LANES = 128                     # a head's block is [block, hd] at its lane offset: whole lanes
_MASK = -0.7 * float(jnp.finfo(jnp.float32).max)       # a ruled-out score
_NT = (((1,), (1,)), ((), ()))  # [a, d] x [b, d] -> [a, b]


# ----------------------------------------------------- the mask, the schedule
@dataclass(frozen=True, eq=False)
class Mask:
    """``bounds`` int32 [4, S], rows ``lo0, hi0, lo1, hi1``: query i sees the
    keys of ``[lo0_i, hi0_i) u [lo1_i, hi1_i)``, the intervals in order and
    inside the sequence (``0 <= lo0 <= hi0 <= lo1 <= hi1 <= S``); an empty
    interval has ``lo == hi``."""
    bounds: np.ndarray

    def __post_init__(self):
        b = np.asarray(self.bounds)
        if b.ndim != 2 or b.shape[0] != 4 or not np.issubdtype(b.dtype, np.integer):
            raise ValueError(f"a mask is an integer [4, S] array of interval bounds, not {b.dtype}{list(b.shape)}")
        edges = np.concatenate([np.zeros((1, b.shape[1]), b.dtype), b, np.full((1, b.shape[1]), b.shape[1])])
        if (np.diff(edges, axis=0) < 0).any():
            raise ValueError("a mask's intervals are in order and inside the sequence: "
                             "0 <= lo0 <= hi0 <= lo1 <= hi1 <= S")
        object.__setattr__(self, "bounds", np.ascontiguousarray(b, np.int32))

    @property
    def seq(self) -> int:
        return self.bounds.shape[1]

    def dense(self) -> np.ndarray:
        """[S, S] bool: True = the query (row) sees the key (column)."""
        key = np.arange(self.seq)[None, :]
        lo0, hi0, lo1, hi1 = self.bounds[:, :, None]
        return ((key >= lo0) & (key < hi0)) | ((key >= lo1) & (key < hi1))


@lru_cache(maxsize=64)
def causal_mask(seq: int, window=None) -> Mask:
    """Query i sees key j when ``j <= i`` and, with ``window``, ``i - j < window``."""
    i = np.arange(seq)
    lo = np.zeros(seq, np.int64) if window is None else np.maximum(i - window + 1, 0)
    return Mask(np.stack([lo, i + 1, i + 1, i + 1]))


def mask_of(allowed: np.ndarray) -> Mask:
    """The description of a dense ``[S, S]`` bool mask (True = the query, a
    row, sees the key, a column); ``ValueError`` when a query's keys are more
    than two runs."""
    a = np.asarray(allowed, bool)
    if a.ndim != 2 or a.shape[0] != a.shape[1]:
        raise ValueError(f"a dense mask is [S, S], not {list(a.shape)}")
    seq = a.shape[0]
    step = np.diff(np.pad(a.astype(np.int8), ((0, 0), (1, 1))), axis=1)     # +1 a run starts, -1 it ends
    runs = (step == 1).sum(1)
    if runs.max(initial=0) > 2:
        i = int(np.argmax(runs > 2))
        raise ValueError(f"query {i} sees {int(runs[i])} separate runs of keys: a mask holds two "
                         f"intervals a query, this one cannot be described")
    bounds = np.zeros((4, seq), np.int32)
    for row, edge in ((0, 1), (1, -1)):
        q, at = np.nonzero(step == edge)                   # in row-major order: a query's first run first
        first = np.concatenate([[True], q[1:] != q[:-1]])
        bounds[row, q[first]] = at[first]
        bounds[row + 2, q[~first]] = at[~first]
    bounds[2:, runs < 2] = bounds[1, runs < 2]             # no second run: empty, at the first one's end
    return Mask(bounds)


IDLE, PLAIN = 0, 1                 # a step of a visit: past its end; a block pair wholly allowed;
#                                    1 + m: a pair cut by the bounds of m's bits (lo0 1, hi0 2, lo1 4, hi1 8)


@dataclass(frozen=True, eq=False)
class Schedule:
    """What the kernels' grids and index maps are built from.  ``q_visits``:
    two int32 tables [nq x q_steps] — the key block that step t of query
    block i visits (past the visit the last one again: nothing is fetched)
    and the step's kind, :data:`IDLE`, :data:`PLAIN` or 1 + the bounds that
    cut the pair; ``k_visits``: a key block's query blocks, the same way;
    ``kinds``: the kinds that occur, :data:`IDLE` left out."""
    block: int
    bounds: np.ndarray              # the mask's, [4, S]
    q_steps: int
    q_visits: tuple
    k_steps: int
    k_visits: tuple
    kinds: tuple
    visits: int                     # block pairs one head's forward visits


def _visit_tables(kind: np.ndarray):
    """(steps, (blocks, kinds)) of the lists that the rows of ``kind`` (a
    block pair's, :data:`IDLE` = not visited) hold, ascending."""
    steps = max(int((kind != IDLE).sum(1).max()), 1)
    blocks, kinds = np.zeros((2, len(kind), steps), np.int32)
    for i, row in enumerate(kind):
        at = np.flatnonzero(row != IDLE)
        if len(at):
            blocks[i, :len(at)], blocks[i, len(at):] = at, at[-1]
            kinds[i, :len(at)] = row[at]
    return steps, (blocks.reshape(-1), kinds.reshape(-1))


@lru_cache(maxsize=64)
def schedule(mask: Mask, block: int = BLOCK) -> Schedule:
    """The blocks ``mask`` makes the kernels visit, from shapes alone."""
    seq = mask.seq
    if seq % block:
        raise ValueError(f"a sequence of {seq} positions is not whole blocks of {block}")
    nq = seq // block
    first, end = np.arange(nq, dtype=np.int64) * block, np.arange(1, nq + 1, dtype=np.int64) * block
    of_block = lambda a, how: how(a.reshape(nq, block, nq), axis=1)        # [S, key block] -> [query block, key block]
    pairs, cuts = 0, 0
    for c in (0, 2):                                        # an interval: its keys in each key block, a query
        lo, hi = mask.bounds[c:c + 2].astype(np.int64)[:, :, None]
        keys = np.clip(np.minimum(hi, end) - np.maximum(lo, first), 0, None)
        pairs = pairs + of_block(keys, np.sum)
        # where some query has a key there, the bounds that rule a key of the block out for some query
        cuts = cuts + of_block(keys > 0, np.any) * ((1 << c) * of_block(lo > first, np.any) +
                                                    (2 << c) * of_block(hi < end, np.any))
    kind = np.where(pairs == 0, IDLE, np.where(pairs == block * block, PLAIN, 1 + cuts))
    return Schedule(block, mask.bounds, *_visit_tables(kind), *_visit_tables(kind.T),
                    tuple(int(k) for k in np.unique(kind[kind != IDLE])), int((kind != IDLE).sum()))


def visited_key_blocks(seq: int, block: int = BLOCK, window=None, mask: Mask = None) -> int:
    """Key blocks one head's forward visits over a sequence of ``seq``:
    causal (``window`` keys back when given), or under ``mask``."""
    if mask is None:
        mask = causal_mask(seq, _whole_window(seq, block, window))
    return schedule(mask, block).visits


def _whole_window(seq: int, block: int, window):
    """``window`` when it cuts anything (None: a full layer), whole blocks."""
    if window is None or window >= seq:
        return None
    if window % block:
        raise ValueError(f"a window of {window} keys is not whole blocks of {block}")
    return window


def _allowed(bounds_ref, j, block, cuts, transposed=False):
    """[block, block] bool: which (query, key) pairs the mask allows between
    the query block whose intervals ``bounds_ref`` holds ([bound, block,
    lanes], every lane alike) and key block ``j``, which the bounds of
    ``cuts``' bits cut — keys x queries when ``transposed``, the intervals
    then [bound, block]."""
    key = j * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0 if transposed else 1)
    bound = (lambda c: bounds_ref[c:c + 1, :]) if transposed else \
        (lambda c: _lanes(bounds_ref[c], block))
    terms = []
    for c in (0, 2):                                        # an interval: of its two bounds those that cut
        tests = ([key >= bound(c)] if cuts >> c & 1 else []) + \
            ([key < bound(c + 1)] if cuts >> (c + 1) & 1 else [])
        if tests:
            terms.append(reduce(jnp.logical_and, tests))
    return reduce(jnp.logical_or, terms)


def _visit_if(kind, kinds, visit):
    """Run ``visit(cuts)`` when the step is inside the visit: the variant of
    the step's kind, of the ``kinds`` the schedule holds — plain (``cuts`` 0)
    on a block pair wholly allowed, masked by the bounds that cut it elsewhere."""
    for k in kinds:
        pl.when(kind == k)(partial(visit, k - PLAIN))


def _lanes(x, width):
    """[rows, lanes] (every lane alike) -> [rows, width]."""
    reps = -(-width // x.shape[1])
    return x if width == x.shape[1] else jnp.tile(x, (1, reps))[:, :width]


def _call(kernel, name, semantics, grid, in_specs, out_specs, out_shape, scratch_shapes, interpret):
    """A kernel over ``grid`` whose index maps and body read a direction's two
    tables of the schedule (scalar prefetch, the first operands)."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        name=name, interpret=interpret)


_WALK = ("parallel", "parallel", "parallel", "arbitrary")  # the last grid axis walks a visit


# ------------------------------------------------------------------ forward
def _kv_refs(refs, split):
    """(loaders of a visit's key and value blocks, the kernel's other refs):
    one stacked ``[2, block, hd]`` operand, or (``split``) two of their own
    widths."""
    if split:
        k_ref, v_ref, *rest = refs
        return (lambda: k_ref[...]), (lambda: v_ref[...]), rest
    kv_ref, *rest = refs
    return (lambda: kv_ref[0]), (lambda: kv_ref[1]), rest


def _fwd_kernel(blocks_ref, kinds_ref, q_ref, *refs, block, steps, kinds, split):
    k, v, (bounds_ref, o_ref, lse_ref, m_s, l_s, acc_s) = _kv_refs(refs, split)
    i, t = pl.program_id(1), pl.program_id(3)
    at = i * steps + t

    @pl.when(t == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, _MASK)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def visit(cuts):
        s = jax.lax.dot_general(q_ref[...], k(), _NT, preferred_element_type=jnp.float32)
        if cuts:
            s = jnp.where(_allowed(bounds_ref, blocks_ref[at], block, cuts), s, _MASK)
        m_prev = m_s[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, block))
        alpha = jnp.exp(m_prev - m_next)
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=-1, keepdims=True)
        m_s[...] = m_next
        acc_s[...] = _lanes(alpha, acc_s.shape[1]) * acc_s[...] + jnp.dot(
            p.astype(q_ref.dtype), v(), preferred_element_type=jnp.float32)

    _visit_if(kinds_ref[at], kinds, visit)

    @pl.when(t == steps - 1)
    def _():
        # a query that saw no key: output 0, and a log-sum-exp under which the
        # backward's probabilities are 0
        m, l = m_s[...], l_s[...]
        dead = m == _MASK
        o_ref[...] = acc_s[...] * _lanes(jnp.where(dead, 0.0, 1.0 / l), acc_s.shape[1])
        # the log-sum-exp leaves as a row, as the backward reads it: the
        # diagonal of the column's copies along the lanes, summed down the rows
        lse = _lanes(jnp.where(dead, -_MASK, m + jnp.log(l)), block)
        eye = jax.lax.broadcasted_iota(jnp.int32, lse.shape, 0) == \
            jax.lax.broadcasted_iota(jnp.int32, lse.shape, 1)
        lse_ref[...] = jnp.sum(jnp.where(eye, lse, 0.0), axis=0, keepdims=True)


def _specs(plan: Schedule, seq, hd, dv, r, split):
    """(a query block, the visit's key and value blocks, the query block's
    intervals and those as an operand, a query block's output) for grid point
    (sequence, query block, query head, step): the heads of a query block
    share its intervals, which are fetched once for them all.  The key and
    value blocks are one ``[2, block, hd]`` block of the stacked operand, or
    (``split``) a ``[block, hd]`` and a ``[block, dv]`` one."""
    block, steps, lanes = plan.block, plan.q_steps, min(128, plan.block)
    bounds = plan.bounds[:max(1, (max(plan.kinds) - PLAIN).bit_length())]    # up to the last that cuts a pair
    visit = lambda b, i, h, t, blocks, kinds: (b, blocks[i * steps + t], h // r)
    at_q = pl.BlockSpec((None, block, hd), lambda b, i, h, t, *_: (b, i, h))
    if split:
        at_kv = [pl.BlockSpec((None, block, hd), visit), pl.BlockSpec((None, block, dv), visit)]
        at_o = pl.BlockSpec((None, block, dv), lambda b, i, h, t, *_: (b, i, h))
    else:
        at_kv = [pl.BlockSpec((None, 2, block, hd),
                              lambda b, i, h, t, blocks, kinds: (b, 0, blocks[i * steps + t], h // r))]
        at_o = at_q
    return (at_q, at_kv,
            pl.BlockSpec((len(bounds), block, lanes), lambda b, i, h, t, *_: (0, i, 0)),
            jnp.broadcast_to(jnp.asarray(bounds)[:, :, None], (len(bounds), seq, lanes)), at_o)


def _widths(q, kv, heads):
    """(q/k channels a head, v channels a head, key-value heads) of the
    kernels' operands: ``kv`` is (the stacked ``[n, 2, S, KV x hd]``,) or
    (k ``[n, S, KV x hd]``, v ``[n, S, KV x dv]``)."""
    hd = q.shape[2] // heads
    groups = kv[0].shape[-1] // hd
    return hd, (hd if len(kv) == 1 else kv[1].shape[2] // groups), groups


def _forward(q, kv, plan: Schedule, heads, interpret):
    n, seq, width = q.shape
    (hd, dv, groups), block = _widths(q, kv, heads), plan.block
    nq, lanes = seq // block, min(128, block)
    split = len(kv) == 2
    at_q, at_kv, at_bounds, bounds, at_o = _specs(plan, seq, hd, dv, heads // groups, split)
    o, lse = _call(
        partial(_fwd_kernel, block=block, steps=plan.q_steps, kinds=plan.kinds, split=split),
        "blocked_attention_fwd", _WALK, (n, nq, heads, plan.q_steps),
        [at_q, *at_kv, at_bounds],
        [at_o, pl.BlockSpec((None, None, 1, block), lambda b, i, h, t, *_: (b, h, 0, i))],
        [jax.ShapeDtypeStruct((n, seq, heads * dv), jnp.float32),
         jax.ShapeDtypeStruct((n, heads, 1, seq), jnp.float32)],
        [pltpu.VMEM((block, lanes), jnp.float32), pltpu.VMEM((block, lanes), jnp.float32),
         pltpu.VMEM((block, dv), jnp.float32)], interpret,
    )(*plan.q_visits, q, *kv, bounds)
    return o, lse[:, :, 0]


# ----------------------------------------------------------------- backward
def _dq_kernel(blocks_ref, kinds_ref, q_ref, *refs, block, steps, kinds, split):
    k_of, v_of, (bounds_ref, do_ref, rows_ref, dq_ref, acc_s) = _kv_refs(refs, split)
    i, t = pl.program_id(1), pl.program_id(3)
    at = i * steps + t

    @pl.when(t == 0)
    def _():
        acc_s[...] = jnp.zeros_like(acc_s)

    def visit(cuts):
        k = k_of()
        s = jax.lax.dot_general(q_ref[...], k, _NT, preferred_element_type=jnp.float32)
        if cuts:
            s = jnp.where(_allowed(bounds_ref, blocks_ref[at], block, cuts), s, _MASK)
        p = jnp.exp(s - jnp.expand_dims(rows_ref[0], -1))   # the rows' log-sum-exp
        dp = jax.lax.dot_general(do_ref[...], v_of(), _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.expand_dims(rows_ref[1], -1))    # their delta
        acc_s[...] += jnp.dot(ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    _visit_if(kinds_ref[at], kinds, visit)

    @pl.when(t == steps - 1)
    def _():
        dq_ref[...] = acc_s[...]


def _dkv_kernel(blocks_ref, kinds_ref, q_ref, *refs, block, steps, kinds, split):
    k, v, (bounds_ref, do_ref, rows_ref, *out) = _kv_refs(refs, split)
    outs, accs = out[:len(out) // 2], out[len(out) // 2:]
    # where dk and dv add up: the two halves of one [2, block, hd] block, or two blocks
    (dk_s, dk_at), (dv_s, dv_at) = ((accs[0], ...), (accs[1], ...)) if split else \
        ((accs[0], 0), (accs[0], 1))
    j, head, t = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((head == 0) & (t == 0))
    def _():
        for acc_s in accs:
            acc_s[...] = jnp.zeros_like(acc_s)

    def visit(cuts):
        s = jax.lax.dot_general(k(), q_ref[...], _NT, preferred_element_type=jnp.float32)
        if cuts:
            s = jnp.where(_allowed(bounds_ref, j, block, cuts, transposed=True), s, _MASK)
        p = jnp.exp(s - rows_ref[0:1, :])                   # [keys, queries] - [1, queries]: the log-sum-exp
        dv_s[dv_at] += jnp.dot(p.astype(do_ref.dtype), do_ref[...], preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v(), do_ref[...], _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - rows_ref[1:2, :])                    # the queries' delta
        dk_s[dk_at] += jnp.dot(ds.astype(q_ref.dtype), q_ref[...], preferred_element_type=jnp.float32)

    _visit_if(kinds_ref[j * steps + t], kinds, visit)

    @pl.when((head == pl.num_programs(3) - 1) & (t == steps - 1))
    def _():
        for out_ref, acc_s in zip(outs, accs):
            out_ref[...] = acc_s[...]


def _backward(q, kv, o, lse, do, plan: Schedule, heads, interpret):
    n, seq, width = q.shape
    (hd, dv, groups), block = _widths(q, kv, heads), plan.block
    r = heads // groups
    nq = seq // block
    split = len(kv) == 2
    delta = jnp.sum((do * o).reshape(n, seq, heads, dv), axis=-1).transpose(0, 2, 1)
    rows, do = jnp.stack([lse, delta], axis=2), do.astype(q.dtype)    # [n, heads, 2, S]: two rows a block

    at_q, at_kv, at_bounds, bounds, at_o = _specs(plan, seq, hd, dv, r, split)
    rows_q = pl.BlockSpec((None, None, 2, block), lambda b, i, h, t, *_: (b, h, 0, i))
    dq = _call(
        partial(_dq_kernel, block=block, steps=plan.q_steps, kinds=plan.kinds, split=split),
        "blocked_attention_dq", _WALK, (n, nq, heads, plan.q_steps),
        [at_q, *at_kv, at_bounds, at_o, rows_q], at_q,
        jax.ShapeDtypeStruct((n, seq, width), jnp.float32),
        [pltpu.VMEM((block, hd), jnp.float32)], interpret,
    )(*plan.q_visits, q, *kv, bounds, do, rows)

    # grid point (sequence, key-value head, key block, query head of the r
    # that share it, step): a key block's query blocks, a head after the other
    steps = plan.k_steps
    of_head = lambda b, g, j, h, t, blocks, kinds: (b, blocks[j * steps + t], g * r + h)
    q_of = pl.BlockSpec((None, block, hd), of_head)
    rows_of = pl.BlockSpec((None, None, 2, block), lambda b, g, j, h, t, blocks, kinds:
                           (b, g * r + h, 0, blocks[j * steps + t]))
    bounds_of = pl.BlockSpec((4, block), lambda b, g, j, h, t, blocks, kinds:
                             (0, blocks[j * steps + t]))
    if split:
        at_kv = [pl.BlockSpec((None, block, c), lambda b, g, j, h, t, *_: (b, j, g)) for c in (hd, dv)]
        do_of, scratch = pl.BlockSpec((None, block, dv), of_head), [(block, hd), (block, dv)]
    else:
        at_kv = [pl.BlockSpec((None, 2, block, hd), lambda b, g, j, h, t, *_: (b, 0, j, g))]
        do_of, scratch = q_of, [(2, block, hd)]
    grads = _call(
        partial(_dkv_kernel, block=block, steps=steps, kinds=plan.kinds, split=split),
        "blocked_attention_dkv", _WALK + ("arbitrary",), (n, groups, nq, r, steps),
        [q_of, *at_kv, bounds_of, do_of, rows_of], at_kv,
        [jax.ShapeDtypeStruct(a.shape, jnp.float32) for a in kv],
        [pltpu.VMEM(shape, jnp.float32) for shape in scratch], interpret,
    )(*plan.k_visits, q, *kv, plan.bounds, do, rows)
    return (dq, *grads) if split else (dq, grads[0][:, 0], grads[0][:, 1])


def _lane_width(hd: int, interpret: bool) -> int:
    """A head's channels as the kernels take them: Mosaic takes a ``[block,
    hd]`` block of a ``[.., heads x hd]`` array only at whole lanes, the
    interpreter at any width."""
    return hd if interpret else -(-hd // LANES) * LANES


def lane_width(channels: int) -> int:
    """The channels a head of ``channels`` is given in the kernels on this
    backend (:func:`blocked_attention` without ``interpret``): a caller that
    builds q and k at this width, zeros after its ``channels``, hands them
    over with ``qk_dim=channels`` and nothing pads them again."""
    return _lane_width(channels, jax.default_backend() != "tpu")


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attend(q, k, v, plan, heads, interpret):
    return _attend_fwd(q, k, v, plan, heads, interpret)[0]


def _attend_fwd(q, k, v, plan, heads, interpret):
    dt = mxu_operand_dtype(q)
    if k.shape == v.shape:          # once, not a block; [n, 2, S, KV x hd]
        q, kv = q.astype(dt), (jnp.stack([k, v], axis=1).astype(dt),)
    else:                           # v at its own width
        q, kv = q.astype(dt), (k.astype(dt), v.astype(dt))
    o, lse = _forward(q, kv, plan, heads, interpret)
    return o, (q, kv, o, lse)


def _attend_bwd(plan, heads, interpret, res, do):
    q, kv, o, lse = res
    return tuple(g.astype(do.dtype) for g in _backward(q, kv, o, lse, do, plan, heads, interpret))


_attend.defvjp(_attend_fwd, _attend_bwd)


def blocked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, window=None,
                      block: int = BLOCK, interpret=None, mask: Mask = None,
                      qk_dim: int = None) -> jnp.ndarray:
    """Grouped-query attention under ``mask``; without one causal, ``window``
    keys back when given.

    q [n, S, KV, R, hd], k [n, S, KV, hd], v [n, S, KV, dv], f32 -> [n, S, KV,
    R, dv] f32: ``softmax_j(q_i . k_j / sqrt(hd)) v_j`` over the keys j that
    ``mask`` gives query i (0 where it gives none) — without a mask the keys
    ``j <= i`` (and ``i - j < window``).  ``qk_dim``: q and k hold that many
    real channels a head and zeros after them (:func:`lane_width`), and the
    scale is ``1 / sqrt(qk_dim)``.  S is a multiple of ``block``, and so is a
    ``window`` shorter than S (a longer one is a full layer).  ``interpret``
    None: the Pallas interpreter anywhere but on a TPU."""
    n, seq, kv, r, hd = q.shape
    dv = v.shape[-1]
    if mask is None:
        mask = causal_mask(seq, _whole_window(seq, block, window))
    elif window is not None or mask.seq != seq:
        raise ValueError(f"a mask of {mask.seq} positions and window {window} for a sequence "
                         f"of {seq}: a mask says it all, and for every position")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    attend = partial(_attend, plan=schedule(mask, block), heads=kv * r, interpret=bool(interpret))
    width, v_width = _lane_width(hd, interpret), _lane_width(dv, interpret)
    # zero channels: they add 0 to every score, the output's are cut off
    pad = lambda x, to: jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, to - x.shape[-1]),))
    if width > hd:
        q, k = pad(q, width), pad(k, width)
    if v_width > dv:
        v = pad(v, v_width)
    q, k, v = (q.reshape(n, seq, kv * r * width) * (1.0 / math.sqrt(qk_dim or hd)),
               k.reshape(n, seq, kv * width), v.reshape(n, seq, kv * v_width))
    if interpret and n > 1:
        # the interpreter copies every operand whole at each grid step: a sequence a call
        o = jax.lax.map(lambda one: attend(*(a[None] for a in one))[0], (q, k, v))
    else:
        o = attend(q, k, v)
    o = o.reshape(n, seq, kv, r, v_width)
    return o[..., :dv] if v_width > dv else o
