"""Plain reference of the ``lfm2_moe`` tower (LiquidAI's LFM2 mixtures of
experts) over rows packed into sequences: gated short convolutions and full
causal attention after ``layer_types``, dense SwiGLU layers first, then
sigmoid-routed experts with a selection bias; forward, next-token loss,
gradients, Adam's first step and the selection bias's rule in straightforward
``jax.numpy``, float32, under ``jax.default_matmul_precision("highest")``.
The convolution is the explicit sum of its taps, each a shifted copy of the
gated input; attention is dense masked softmax, every allowed and ruled-out
score computed, a block of queries at a time against all keys (``lax.map``,
each block recomputed in the backward pass) so that no ``[S, S]`` array a head
is held at 8,192 positions; every held expert is applied densely to every
position, one after the other (``lax.scan``); the head's cross-entropy a chunk
of positions at a time.  No kernels, no online softmax, no block schedule.

Follows config.json of LiquidAI/LFM2-24B-A2B (``model_type`` ``lfm2_moe``) and
its published modelling code (``Lfm2MoeShortConv``: ``in_proj`` then
``B, C, x = chunk(3)``, a depthwise ``Conv1d`` padded by L - 1 and cut to the
sequence, ``out_proj``)::

    h0 = Embed[ids];  a = RMSNorm_op(h)
    conv:           [B | C | x] = a W_in;  u = B * x
                    c_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t   (L = 3 taps; u before the sequence = 0)
                    o = (C * c) W_out
    full_attention: q = RMSNorm_q(a Wq), k = RMSNorm_k(a Wk) per head of hidden / heads, v = a Wv;
                    rotate_half RoPE(q, k); o = softmax(q k^T / sqrt(hd)) v over j <= i; o Wo
    h = h + o;  m = RMSNorm_ffn(h)
    f = SwiGLU(m) | sum_{e in top-k of (s + b)} w_e SwiGLU_e(m),  s = sigmoid(m Wr),  w = s[chosen] / sum s[chosen]
    h = h + f;  logits = RMSNorm_final(h) W_head

Departures from the published code, all in the configuration's ``assumed``:

- the route's weights are the chosen scores over their sum; the published
  code adds 1e-6 to that sum (under 1e-5 relative at four sigmoid scores);
- the selection bias takes no gradient; after a step ``b <- b +
  load_balance_coeff x sign(mean(n) - n_e)``, ``n_e`` the step's positions
  (``PAD`` ones too) whose top-k holds expert e, over ALL experts; not centred
  (DeepSeek-V3's rule, arXiv:2412.19437 section 2.1.2);
- the *share*: this rank holds experts ``lo .. lo+held``; the router keeps
  every expert's output, the weights are normalised over all top-k, and the
  routed sum runs over the held experts only: what the absent ones would add
  is left out;
- the vocabulary is a slice (``vocab_size`` of the share), logits and loss
  over it;
- the initial parameters, the tokenisation, the packing (R rows laid end to
  end, ``PAD`` to whole blocks; a later row sees the earlier ones, through the
  mask and through the convolution's taps) and the loss (the cross-entropy of
  ``id_{i+1}`` wherever that is not ``PAD``, each weighted by its row's weight,
  over the weighted count).

Independent of ``shifu_tpu``: parameters come in as a nested dict of arrays
under the names the saved tower uses; the shapes are this file's own; the
token ids, the split, the order of an epoch's rows, the packing, Adam's first
step and the bias rule are restated in ``reference/afmoe.py`` (and the files
it imports), which this file imports.

Controls, for the harness to put through the cell's limits: ``lower=True`` (the
same mathematics in bfloat16) and, as keys of ``cfg``: ``capacity_factor``
(dropped pairs), ``taps_reversed`` (w_0 on u_t), ``segment`` (rows not
packed: a position sees its own row's keys and taps only and positions restart
with the row).  For the selection bias's judge, :func:`count_bounds` gives
each expert's count with the fewest and the most that a router whose scores
lie near these could count.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .afmoe import (HEAD_CHUNK, QUERY_BLOCK, _cast, _precision, _rms, _swiglu,  # noqa: F401
                    adam_first_step, allowed, bias_after, epoch_order, flatten, nest, pack,
                    rows_to_ids, special_ids, split_rows, ADAM_B1, ADAM_B2)

FAULTS = ("capacity_factor", "taps_reversed", "segment")
KINDS = ("conv", "full_attention")


# ---------------------------------------------------- what the seed decides
def _routed(cfg) -> int:
    return int(cfg["num_experts"]) * int(cfg.get("expert_parallel_size", 1))


def _head_dim(cfg) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _layer_shapes(layer: int, cfg) -> Dict[str, tuple]:
    d, hd = cfg["hidden_size"], _head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    out = {"norm_op": (d,), "norm_ffn": (d,)}
    if cfg["layer_types"][layer] == KINDS[0]:
        out.update(conv_in=(d, 3 * d), conv_w=(cfg["conv_L_cache"], d), conv_out=(d, d))
    else:
        out.update(norm_q=(hd,), norm_k=(hd,), wq=(d, h * hd), wk=(d, kv * hd), wv=(d, kv * hd),
                   wo=(h * hd, d))
    if layer < cfg["num_dense_layers"]:
        f = cfg["intermediate_size"]
        return {**out, "w_gate_up": (d, 2 * f), "w_down": (f, d)}
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    return {**out, "router": (d, _routed(cfg)), "bias": (_routed(cfg),),
            "we_gate_up": (held, d, 2 * f), "we_down": (held, f, d)}


def param_shapes(cfg) -> Dict[str, tuple]:
    """Flat name -> shape of every array, as the share has them."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": (v, d), "head": (d, v), "norm_f": (d,)}
    for i in range(cfg["num_hidden_layers"]):
        out.update({f"blocks.{i:02d}.{k}": s for k, s in _layer_shapes(i, cfg).items()})
    return out


def init_params(seed: int, cfg) -> dict:
    """What a fresh job starts from (the configuration's ``assumed.init``):
    array ``i`` of the names in sorted order is drawn from ``fold_in(key,
    i)``: normal(0, 0.02) matrices, the convolution's taps U(+-1/sqrt(L)) (a
    depthwise ``Conv1d``'s default), unit norm weights, a zero selection bias."""
    key = jax.random.PRNGKey(seed)
    shapes = param_shapes(cfg)

    def draw(i, name):
        leaf, k = name.rsplit(".", 1)[-1], jax.random.fold_in(key, i)
        if leaf.startswith("norm"):
            return np.ones(shapes[name], np.float32)
        if leaf == "bias":
            return np.zeros(shapes[name], np.float32)
        if leaf == "conv_w":
            bound = 1.0 / np.sqrt(shapes[name][0])
            return np.asarray(jax.random.uniform(k, shapes[name], jnp.float32, -bound, bound))
        return np.asarray(0.02 * jax.random.normal(k, shapes[name], jnp.float32))
    return nest({name: draw(i, name) for i, name in enumerate(sorted(shapes))})


# ------------------------------------------------------------------- layers
def knobs_for(cfg, seq: int, rows: int = 1) -> Dict[str, np.ndarray]:
    """What the layers read beside the weights, as data (so that every control
    runs the program the sound configuration runs): the attention mask [S, S],
    the rotary tables [S, hd], which of the convolution's look-backs reach
    inside the sequence (``keep`` [L, S]: tap ``s`` back at position t), the
    taps' order, and how many pairs a held expert takes of ``rows`` sequences
    (no limit unless ``capacity_factor``)."""
    hd, theta = _head_dim(cfg), float(cfg["rope_parameters"]["rope_theta"])
    segment = cfg.get("segment")
    t = np.arange(seq)
    pos = (t % int(segment) if segment else t).astype(np.float32)
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = np.concatenate([pos[:, None] * inv[None, :]] * 2, -1)
    keep = np.stack([(pos >= s) if segment else (t >= s) for s in range(cfg["conv_L_cache"])])
    cap = 2 ** 30
    if cfg.get("capacity_factor"):
        cap = int(np.ceil(cfg["capacity_factor"] * rows * seq * cfg["num_experts_per_tok"]
                          / _routed(cfg)))
    return {"mask": allowed(seq, None, segment), "cos": np.cos(ang).astype(np.float32),
            "sin": np.sin(ang).astype(np.float32), "keep": keep.astype(np.float32),
            "reverse": np.float32(1.0 if cfg.get("taps_reversed") else 0.0), "cap": np.int32(cap)}


def short_conv(p, a, cfg, keep, reverse):
    """a [n, S, D] (normed) -> [n, S, D]: the gated short convolution, its
    taps summed one by one (tap j reads the position L - 1 - j back)."""
    d, taps = cfg["hidden_size"], cfg["conv_L_cache"]
    bcx = a @ p["conv_in"]
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    u = b * x
    w = jnp.where(reverse > 0, p["conv_w"][::-1], p["conv_w"])
    conv = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :u.shape[1]]
        conv = conv + w[j] * shifted * keep[back][None, :, None].astype(u.dtype)
    return (c * conv) @ p["conv_out"]


def attention(p, a, cfg, mask, cos, sin):
    """a [n, S, D] (normed) -> [n, S, D]: grouped-query attention under
    ``mask`` [S, S], q and k rotated by the tables ``cos`` / ``sin`` [S, hd]."""
    n, s, _ = a.shape
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], _head_dim(cfg)
    eps = cfg.get("norm_eps", 1e-5)
    rotate = lambda x: x * cos[None, :, None, :].astype(x.dtype) + jnp.concatenate(
        [-x[..., hd // 2:], x[..., :hd // 2]], -1) * sin[None, :, None, :].astype(x.dtype)
    q = rotate(_rms((a @ p["wq"]).reshape(n, s, h, hd), p["norm_q"], eps))
    k = rotate(_rms((a @ p["wk"]).reshape(n, s, kv, hd), p["norm_k"], eps))
    v = (a @ p["wv"]).reshape(n, s, kv, hd)
    k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)

    @jax.checkpoint
    def block(qb, ok):
        scores = jnp.einsum("nqhd,nkhd->nhqk", qb, k).astype(jnp.float32) / np.float32(np.sqrt(hd))
        probs = jax.nn.softmax(jnp.where(ok[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("nhqk,nkhd->nqhd", probs.astype(v.dtype), v)
    bq = min(QUERY_BLOCK, s)                    # a block of queries at a time, one after the other
    pad = -s % bq
    qs = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(n, -1, bq, h, hd).swapaxes(0, 1)
    oks = jnp.pad(mask, ((0, pad), (0, 0)), constant_values=True).reshape(-1, bq, s)
    o = jax.lax.map(lambda x: block(*x), (qs, oks)).swapaxes(0, 1).reshape(n, -1, h * hd)[:, :s]
    return o @ p["wo"]


def route(p, x, cfg):
    """x [..., D] -> (weights [..., E] of the chosen experts, 0 elsewhere;
    chosen [..., E] bool) over ALL experts."""
    k, e = cfg["num_experts_per_tok"], p["router"].shape[1]
    s = jax.nn.sigmoid((x @ p["router"]).astype(jnp.float32))
    _, top_e = jax.lax.top_k(s + p["bias"].astype(jnp.float32), k)
    chosen = (top_e[..., None] == jnp.arange(e)).any(-2)
    top_s = jnp.where(chosen, s, 0.0)
    if cfg.get("norm_topk_prob", True):
        top_s = top_s / top_s.sum(-1, keepdims=True)
    return top_s * np.float32(cfg.get("routed_scaling_factor", 1.0)), chosen


def count_bounds_of(p, x, cfg, delta):
    """x [..., D] -> [3, E]: the positions whose top-k holds each expert, and
    the fewest and the most that a router could count whose biased scores
    each lie within ``delta`` / 2 of these: a chosen pair can leave only where
    its score lies within ``delta`` of the (k+1)-th, an unchosen one enter only
    where within ``delta`` of the k-th.  The router's product is exact, as
    the program's is."""
    k = cfg["num_experts_per_tok"]
    logits = jnp.dot(x, p["router"], precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits.astype(jnp.float32)) + p["bias"].astype(jnp.float32)
    top, top_e = jax.lax.top_k(s, k + 1)
    chosen = (top_e[..., :k, None] == jnp.arange(s.shape[-1])).any(-2)
    leave = chosen & (s - top[..., k:k + 1] < delta)
    enter = ~chosen & (top[..., k - 1:k] - s < delta)
    total = lambda a: a.reshape(-1, a.shape[-1]).sum(0).astype(jnp.float32)
    n = total(chosen)
    return jnp.stack([n, n - total(leave), n + total(enter)])


def moe_ffn(p, x, cfg, lo: int, cap=2 ** 30):
    """(the held experts ``lo .. lo+held`` applied densely and weighted,
    tokens [E]: the positions whose top-k holds each expert).  A held expert
    takes its first ``cap`` pairs."""
    held = p["we_gate_up"].shape[0]
    w_all, chosen = route(p, x, cfg)
    w_e = w_all[..., lo:lo + held]
    took = (w_e > 0).reshape(-1, held)
    w_e = jnp.where((jnp.cumsum(took, 0) <= cap).reshape(w_e.shape), w_e, 0.0)

    def one(y, e):                              # every held expert on every position, in turn
        w_gu, w_d, w = e
        return y + w[..., None].astype(x.dtype) * _swiglu(x, w_gu, w_d), None
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x),
                        (p["we_gate_up"], p["we_down"], jnp.moveaxis(w_e, -1, 0)))
    return y, chosen.reshape(-1, chosen.shape[-1]).sum(0).astype(jnp.float32)


def trunk(params, ids, cfg, lo: int, knobs=None):
    """ids [n, S] -> (the last layer's output [n, S, D] before ``norm_f``,
    tokens [MoE layers, E]); ``knobs``: :func:`knobs_for`'s (``cfg``'s own
    when None); with a knob ``delta``, tokens are :func:`count_bounds_of`'s
    [MoE layers, 3, E]."""
    eps = cfg.get("norm_eps", 1e-5)
    kn = knobs_for(cfg, ids.shape[1], ids.shape[0]) if knobs is None else knobs

    def layer(i):
        @jax.checkpoint
        def fn(h, p, mask, cos, sin, keep, reverse, delta):
            a = _rms(h, p["norm_op"], eps)
            if cfg["layer_types"][i] == KINDS[0]:
                h = h + short_conv(p, a, cfg, keep, reverse)
            else:
                h = h + attention(p, a, cfg, mask, cos, sin)
            m = _rms(h, p["norm_ffn"], eps)
            if i < cfg["num_dense_layers"]:
                f, tokens = _swiglu(m, p["w_gate_up"], p["w_down"]), None
            else:
                f, tokens = moe_ffn(p, m, cfg, lo, kn["cap"])
                if delta is not None:
                    tokens = count_bounds_of(p, m, cfg, delta)
            return h + f, tokens
        return fn
    h = params["embed"][ids]
    found = []
    data = [jnp.asarray(kn[k]) for k in ("mask", "cos", "sin", "keep", "reverse")] + [kn.get("delta")]
    for i, name in enumerate(sorted(params["blocks"])):
        h, tokens = layer(i)(h, params["blocks"][name], *data)
        if tokens is not None:
            found.append(tokens)
    return h, jnp.stack(found)


def sequence_loss(params, ids, weights, pad_id, cfg, lo: int, knobs=None):
    """(sum over targets of weight x CE(logits_i, id_{i+1}), the weights' sum,
    tokens [MoE layers, E]); a target is every non-``PAD`` id but the first."""
    h, tokens = trunk(params, ids, cfg, lo, knobs)
    hidden = _rms(h[:, :-1], params["norm_f"], cfg.get("norm_eps", 1e-5))
    targets = ids[:, 1:]
    w = jnp.where(targets != pad_id, weights[:, 1:], 0.0)

    @jax.checkpoint
    def chunk(a):                               # a chunk of positions' logits at a time
        hid, tgt, wt = a
        logits = (hid @ params["head"]).astype(jnp.float32)
        ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
        return jnp.sum(ce * wt)
    n, t = targets.shape
    pad = -t % HEAD_CHUNK
    cut = lambda x: jnp.moveaxis(jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)
                                         ).reshape((n, -1, HEAD_CHUNK) + x.shape[2:]), 1, 0)
    return jnp.sum(jax.lax.map(chunk, (cut(hidden), cut(targets), cut(w)))), (jnp.sum(w), tokens)


# --------------------------------------------------------------- the checks
def loss_and_grads(params, ids: np.ndarray, weights: np.ndarray, pad_id: int, cfg, lo: int,
                   lower: bool = False) -> Tuple[float, dict, np.ndarray]:
    """The microbatch's loss over its packed sequences (ids, weights [n, L]),
    its gradient for every parameter and tokens [MoE layers, E] (the
    microbatch's counts), one sequence at a time."""
    params = _cast(params, lower)
    clean = {k: v for k, v in cfg.items() if k not in FAULTS}       # the faults go in as data
    fn = jax.jit(jax.value_and_grad(
        lambda p, a, w, pad, kn: sequence_loss(p, a, w, pad, clean, lo, kn), has_aux=True))
    knobs = jax.tree_util.tree_map(jnp.asarray, knobs_for(cfg, ids.shape[1]))
    total = count = 0.0
    grads = tokens = None
    with _precision(lower):
        for a in range(len(ids)):
            (l, (c, t)), g = fn(params, jnp.asarray(ids[a:a + 1], jnp.int32),
                                jnp.asarray(weights[a:a + 1], jnp.float32), jnp.int32(pad_id), knobs)
            total, count = total + float(l), count + float(c)
            # summed on the host: the device holds one sequence's gradients, never two
            g = jax.tree_util.tree_map(lambda v: np.asarray(v.astype(jnp.float32)), g)
            grads = g if grads is None else jax.tree_util.tree_map(np.add, grads, g)
            tokens = np.asarray(t) if tokens is None else tokens + np.asarray(t)
            del g
    return total / count, jax.tree_util.tree_map(lambda v: v / np.float32(count), grads), tokens


def count_bounds(params, ids: np.ndarray, cfg, lo: int, delta: float) -> np.ndarray:
    """[MoE layers, 3, E] of the microbatch's packed sequences ``ids`` [n, L]:
    each expert's count, and the fewest and the most a router could count
    whose every biased score lies within ``delta`` / 2 of this one's
    (:func:`count_bounds_of`), one sequence at a time."""
    params = _cast(params, False)
    clean = {k: v for k, v in cfg.items() if k not in FAULTS}
    fn = jax.jit(lambda p, a, kn: trunk(p, a, clean, lo, kn)[1])
    knobs = jax.tree_util.tree_map(jnp.asarray, {**knobs_for(cfg, ids.shape[1]),
                                                 "delta": np.float32(delta)})
    out = 0.0
    with _precision(False):
        for a in range(len(ids)):
            out = out + np.asarray(fn(params, jnp.asarray(ids[a:a + 1], jnp.int32), knobs))
    return out


def tag_logit_difference(params, bins: np.ndarray, cfg, lo: int, column_bins,
                         rows_per_block: int = 16, lower: bool = False) -> np.ndarray:
    """``eval``'s quantity for each row: one causal forward over the feature
    tokens, one row a sequence, logit_TAG1 - logit_TAG0 at the last of them."""
    sp = special_ids(column_bins)
    ids = rows_to_ids(bins, np.zeros(len(bins)), column_bins)[:, :-1]
    params = _cast(params, lower)
    clean = {k: v for k, v in cfg.items() if k not in FAULTS}

    @jax.jit
    def fn(p, a, tag0, kn):
        h = _rms(trunk(p, a, clean, lo, kn)[0][:, -1], p["norm_f"], cfg.get("norm_eps", 1e-5))
        two = (h @ jax.lax.dynamic_slice_in_dim(p["head"], tag0, 2, axis=1)).astype(jnp.float32)
        return two[:, 1] - two[:, 0]
    out = []
    with _precision(lower):
        for a in range(0, len(ids), rows_per_block):
            part = ids[a: a + rows_per_block]
            knobs = jax.tree_util.tree_map(jnp.asarray, knobs_for(cfg, part.shape[1], len(part)))
            out.append(np.asarray(fn(params, jnp.asarray(part, jnp.int32), jnp.int32(sp["TAG0"]),
                                     knobs)))
    return np.concatenate(out)


def forward_logits(params, ids: np.ndarray, cfg, lo: int) -> np.ndarray:
    """Next-token logits [n, S, V] of packed sequences: the tests' comparison."""
    with _precision(False):
        p = _cast(params, False)
        h, _ = trunk(p, jnp.asarray(ids, jnp.int32), cfg, lo)
        return np.asarray(_rms(h, p["norm_f"], cfg.get("norm_eps", 1e-5)) @ p["head"])
