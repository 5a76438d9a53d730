"""Plain reference of the ``afmoe`` tower (Arcee's Trinity family) over rows
packed into sequences: window and full causal attention after ``layer_types``,
per-head q/k norms, a sigmoid gate on the attention output, a norm before and
after every sub-layer, dense SwiGLU layers first, then sigmoid-routed experts
with one shared expert; forward, next-token loss, gradients, Adam's first
step and the selection bias's rule in straightforward ``jax.numpy``, float32,
under ``jax.default_matmul_precision("highest")``.  Attention is dense masked
softmax, every allowed and ruled-out score computed, a block of queries at a
time against all keys (``lax.map``; each block recomputed in the backward pass,
as each layer is) so that no ``[S, S]`` array a head is held at 8,192
positions; every held expert is applied densely to every position, one after
the other (``lax.scan``); the head's cross-entropy a chunk of positions at a
time.  No kernels, no online softmax, no block schedule.

Follows config.json of arcee-ai/Trinity-Mini (``model_type`` ``afmoe``)::

    h0 = Embed[ids] sqrt(d)
    a = RMSNorm_in(h); q = RMSNorm_q(a Wq), k = RMSNorm_k(a Wk) per head, v = a Wv
    sliding_attention: rotate_half RoPE(q, k), key j allowed when 0 <= i - j < sliding_window
    full_attention:    no rotary,              key j allowed when j <= i
    h = h + RMSNorm_post_attn((softmax(q k^T / sqrt(hd)) v * sigmoid(a Wg)) Wo)
    m = RMSNorm_pre_mlp(h)
    f = SwiGLU(m) | SwiGLU_shared(m) + sum_{e in top-k of (s + b)} w_e SwiGLU_e(m),
        s = sigmoid(m Wr), w = s[chosen] / (sum s[chosen] + 1e-20) x route_scale
    h = h + RMSNorm_post_mlp(f);  logits = RMSNorm_final(h) W_head

Departures from what config.json states, all in the configuration's ``assumed``:

- the per-head q/k norms, the output gate, the four norms' placement, no
  rotary on full layers and ``sqrt(d)`` on the embedding are the published
  ``afmoe`` modelling code's, not config.json's keys;
- the selection bias takes no gradient; after a step ``b <- b +
  load_balance_coeff x sign(mean(n) - n_e)``, ``n_e`` the step's positions
  (``PAD`` ones too) whose top-k holds expert e, over ALL experts; not centred
  (DeepSeek-V3's rule, arXiv:2412.19437 section 2.1.2);
- the initial parameters, the tokenisation, the packing (R rows laid end to
  end, ``PAD`` to whole blocks; a later row sees the earlier ones) and the
  loss (the cross-entropy of ``id_{i+1}`` wherever that is not ``PAD``, each
  weighted by its row's weight, over the weighted count);
- the *share*: this rank holds experts ``lo .. lo+held`` and a slice of the
  vocabulary; the router keeps every expert's output, the weights are
  normalised over all top-k; what the absent experts would add is left out.

Independent of ``shifu_tpu``: parameters come in as a nested dict of arrays
under the names the saved tower uses; the token ids, the split, the order of
an epoch's rows and Adam's first step are restated in ``reference/sdar_moe.py``
and ``reference/nemotron_h.py``, which this file imports.

Controls, for the driver to put through its own limits: ``lower=True`` (the
same mathematics in bfloat16) and, as keys of ``cfg``: ``capacity_factor``
(dropped pairs), ``all_full`` (every layer full: no window, no rotary),
``window_on_full``, ``rotary_on_full``, ``no_gate``, ``no_post_norms``,
``no_shared``, ``segment`` (rows not packed: a position sees its own row's
only and positions restart with the row).
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .nemotron_h import flatten, nest, rows_to_ids  # noqa: F401
from .sdar_moe import (ADAM_B1, ADAM_B2, adam_first_step, epoch_order,  # noqa: F401
                       special_ids, split_rows)

QUERY_BLOCK = 256
FAULTS = ("capacity_factor", "all_full", "window_on_full", "rotary_on_full", "no_gate",
          "no_post_norms", "no_shared", "segment")
HEAD_CHUNK = 1024
KINDS = ("sliding_attention", "full_attention")


# ------------------------------------------------------------- tokens, rows
def pack(ids: np.ndarray, weights: np.ndarray, rows_per_sequence: int, block: int,
         pad_id: int) -> Tuple[np.ndarray, np.ndarray]:
    """[n, S] rows + [n] weights -> ([n / R, L] ids, [n / R, L] weights): R
    consecutive rows end to end, ``PAD`` (weight 0) up to whole blocks."""
    n, s = ids.shape
    seqs, used = n // rows_per_sequence, rows_per_sequence * s
    length = -(-used // block) * block
    out = np.full((seqs, length), pad_id, np.int64)
    w = np.zeros((seqs, length), np.float32)
    out[:, :used] = ids.reshape(seqs, used)
    w[:, :used] = np.repeat(np.asarray(weights, np.float32)[:, None], s, 1).reshape(seqs, used)
    return out, w


# ---------------------------------------------------- what the seed decides
def _routed(cfg) -> int:
    return int(cfg["num_experts"]) * int(cfg.get("expert_parallel_size", 1))


def _layer_shapes(layer: int, cfg) -> Dict[str, tuple]:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    out = {"norm_in": (d,), "norm_post_attn": (d,), "norm_pre_mlp": (d,), "norm_post_mlp": (d,),
           "norm_q": (hd,), "norm_k": (hd,), "wq": (d, h * hd), "wk": (d, kv * hd),
           "wv": (d, kv * hd), "wg": (d, h * hd), "wo": (h * hd, d)}
    if layer < cfg["num_dense_layers"]:
        f = cfg["intermediate_size"]
        return {**out, "w_gate_up": (d, 2 * f), "w_down": (f, d)}
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    return {**out, "router": (d, _routed(cfg)), "bias": (_routed(cfg),),
            "ws_gate_up": (d, 2 * f), "ws_down": (f, d),
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
    i)``: normal(0, 0.02) matrices, unit norm weights, a zero selection bias."""
    key = jax.random.PRNGKey(seed)
    shapes = param_shapes(cfg)

    def draw(i, name):
        leaf = name.rsplit(".", 1)[-1]
        if leaf.startswith("norm"):
            return np.ones(shapes[name], np.float32)
        if leaf == "bias":
            return np.zeros(shapes[name], np.float32)
        return np.asarray(0.02 * jax.random.normal(jax.random.fold_in(key, i), shapes[name],
                                                   jnp.float32))
    return nest({name: draw(i, name) for i, name in enumerate(sorted(shapes))})


# ------------------------------------------------------------------- layers
def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)).astype(x.dtype) * w


def _swiglu(x, w_gate_up, w_down):
    f = w_down.shape[0]
    return (jax.nn.silu(x @ w_gate_up[:, :f]) * (x @ w_gate_up[:, f:])) @ w_down


def allowed(seq: int, window, segment=None) -> np.ndarray:
    """[S, S] bool: may query i see key j."""
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    ok = j <= i
    if window is not None:
        ok &= i - j < window
    if segment:
        ok &= i // segment == j // segment
    return ok


def knobs_for(cfg, seq: int, rows: int = 1) -> Dict[str, np.ndarray]:
    """What the layers read beside the weights, as data (so that every control
    below runs the program the sound configuration runs): each layer's mask
    [L, S, S] and rotary tables [L, S, hd] (cos 1, sin 0 where a layer applies
    none), whether the gate, the post-norms and the shared expert are there,
    and how many pairs a held expert takes of ``rows`` sequences (no limit
    unless ``capacity_factor``)."""
    hd, theta = cfg["head_dim"], cfg.get("rope_theta", 10000.0)
    segment = cfg.get("segment")
    pos = (np.arange(seq) % int(segment) if segment else np.arange(seq)).astype(np.float32)
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = np.concatenate([pos[:, None] * inv[None, :]] * 2, -1)
    masks, cos, sin = [], [], []
    for kind in cfg["layer_types"]:
        is_full = kind == KINDS[1]
        windowed = not cfg.get("all_full") and (not is_full or cfg.get("window_on_full"))
        rotary = not cfg.get("all_full") and (not is_full or cfg.get("rotary_on_full"))
        masks.append(allowed(seq, int(cfg["sliding_window"]) if windowed else None, segment))
        cos.append(np.cos(ang) if rotary else np.ones_like(ang))
        sin.append(np.sin(ang) if rotary else np.zeros_like(ang))
    cap = 2 ** 30
    if cfg.get("capacity_factor"):
        cap = int(np.ceil(cfg["capacity_factor"] * rows * seq * cfg["num_experts_per_tok"]
                          / _routed(cfg)))
    flag = lambda off: np.float32(0.0 if cfg.get(off) else 1.0)
    return {"mask": np.stack(masks), "cos": np.stack(cos).astype(np.float32),
            "sin": np.stack(sin).astype(np.float32), "gate": flag("no_gate"),
            "post": flag("no_post_norms"), "shared": flag("no_shared"), "cap": np.int32(cap)}


def attention(p, a, cfg, mask, cos, sin, gate):
    """a [n, S, D] (normed) -> [n, S, D]: gated grouped-query attention under
    ``mask`` [S, S], q and k rotated by the tables ``cos`` / ``sin`` [S, hd]."""
    n, s, _ = a.shape
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg.get("rms_norm_eps", 1e-5)
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
    o = o * jnp.where(gate > 0, jax.nn.sigmoid(a @ p["wg"]), jnp.ones((), a.dtype))
    return o @ p["wo"]


def route(p, x, cfg):
    """x [..., D] -> (weights [..., E] of the chosen experts, 0 elsewhere;
    chosen [..., E] bool) over ALL experts."""
    k, e = cfg["num_experts_per_tok"], p["router"].shape[1]
    s = jax.nn.sigmoid((x @ p["router"]).astype(jnp.float32))
    _, top_e = jax.lax.top_k(s + p["bias"].astype(jnp.float32), k)
    chosen = (top_e[..., None] == jnp.arange(e)).any(-2)
    top_s = jnp.where(chosen, s, 0.0)
    if cfg.get("route_norm", True):
        top_s = top_s / (top_s.sum(-1, keepdims=True) + 1e-20)
    return top_s * np.float32(cfg.get("route_scale", 1.0)), chosen


def moe_ffn(p, x, cfg, lo: int, cap=2 ** 30, shared=1.0):
    """(the shared expert + the held experts ``lo .. lo+held`` applied densely
    and weighted, tokens [E]: the positions whose top-k holds each expert).
    A held expert takes its first ``cap`` pairs; ``shared`` 0 leaves the
    shared expert out."""
    held = p["we_gate_up"].shape[0]
    w_all, chosen = route(p, x, cfg)
    w_e = w_all[..., lo:lo + held]
    took = (w_e > 0).reshape(-1, held)
    w_e = jnp.where((jnp.cumsum(took, 0) <= cap).reshape(w_e.shape), w_e, 0.0)
    y = jnp.asarray(shared, x.dtype) * _swiglu(x, p["ws_gate_up"], p["ws_down"])

    def one(y, e):                              # every held expert on every position, in turn
        w_gu, w_d, w = e
        return y + w[..., None].astype(x.dtype) * _swiglu(x, w_gu, w_d), None
    y, _ = jax.lax.scan(jax.checkpoint(one), y, (p["we_gate_up"], p["we_down"], jnp.moveaxis(w_e, -1, 0)))
    return y, chosen.reshape(-1, chosen.shape[-1]).sum(0).astype(jnp.float32)


def trunk(params, ids, cfg, lo: int, knobs=None):
    """ids [n, S] -> (the last layer's output [n, S, D] before ``norm_f``,
    tokens [MoE layers, E]); ``knobs``: :func:`knobs_for`'s (``cfg``'s own
    when None)."""
    eps = cfg.get("rms_norm_eps", 1e-5)
    kn = knobs_for(cfg, ids.shape[1], ids.shape[0]) if knobs is None else knobs
    post = lambda x, w: jnp.where(kn["post"] > 0, _rms(x, w, eps), x)

    def layer(i):
        @jax.checkpoint
        def fn(h, p, mask, cos, sin):
            h = h + post(attention(p, _rms(h, p["norm_in"], eps), cfg, mask, cos, sin, kn["gate"]),
                         p["norm_post_attn"])
            m = _rms(h, p["norm_pre_mlp"], eps)
            if i < cfg["num_dense_layers"]:
                f, tokens = _swiglu(m, p["w_gate_up"], p["w_down"]), None
            else:
                f, tokens = moe_ffn(p, m, cfg, lo, kn["cap"], kn["shared"])
            return h + post(f, p["norm_post_mlp"]), tokens
        return fn
    h = params["embed"][ids]
    if cfg.get("mup_enabled"):
        h = h * jnp.asarray(np.sqrt(cfg["hidden_size"]), h.dtype)
    found = []
    for i, name in enumerate(sorted(params["blocks"])):
        h, tokens = layer(i)(h, params["blocks"][name], jnp.asarray(kn["mask"][i]),
                             jnp.asarray(kn["cos"][i]), jnp.asarray(kn["sin"][i]))
        if tokens is not None:
            found.append(tokens)
    return h, jnp.stack(found)


def sequence_loss(params, ids, weights, pad_id, cfg, lo: int, knobs=None):
    """(sum over targets of weight x CE(logits_i, id_{i+1}), the weights' sum,
    tokens [MoE layers, E]); a target is every non-``PAD`` id but the first."""
    h, tokens = trunk(params, ids, cfg, lo, knobs)
    hidden = _rms(h[:, :-1], params["norm_f"], cfg.get("rms_norm_eps", 1e-5))
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
def _cast(params, lower: bool):
    dt = jnp.bfloat16 if lower else jnp.float32
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a).astype(dt), params)


def _precision(lower: bool):
    return jax.default_matmul_precision("default" if lower else "highest")


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


def bias_after(bias: np.ndarray, tokens: np.ndarray, coeff: float) -> np.ndarray:
    """The selection bias after a step with the counts ``tokens`` [E]."""
    return (bias + np.float32(coeff) * np.sign(tokens.mean() - tokens)).astype(np.float32)


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
        h = _rms(trunk(p, a, clean, lo, kn)[0][:, -1], p["norm_f"], cfg.get("rms_norm_eps", 1e-5))
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
        return np.asarray(_rms(h, p["norm_f"], cfg.get("rms_norm_eps", 1e-5)) @ p["head"])
