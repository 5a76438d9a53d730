"""Plain reference of the ``deepseek_v3`` tower (DeepSeek-V3's architecture,
as Moonlight-16B-A3B configures it) over rows packed into sequences:
multi-head latent attention, a dense SwiGLU layer first, then sigmoid-routed
experts beside two shared ones, a selection bias and a sequence-wise balance
loss; forward, next-token loss with the balance loss, gradients, Adam's first
step and the selection bias's rule in straightforward ``jax.numpy``, float32,
under ``jax.default_matmul_precision("highest")``.  Latent attention is
written unfactored, as its equations state it: every head's key is its own
``k_N`` beside an explicit copy of the shared rotary key, and the score is
dense masked softmax, every allowed and ruled-out score computed, a block of
queries at a time against all keys (``lax.map``; each block recomputed in the
backward pass, as each layer is) so that no ``[S, S]`` array a head is held
at 8,192 positions; every held expert is applied densely to every position,
one after the other (``lax.scan``); the head's cross-entropy a chunk of
positions at a time.  No kernels, no lane layout, no online softmax, no block
schedule.

Follows config.json of moonshotai/Moonlight-16B-A3B (``model_type``
``deepseek_v3``) and DeepSeek-V3 (arXiv:2412.19437, section 2.1)::

    a = RMSNorm_in(h);  q_i = a W_Q,i = [q_N,i ; q_R,i]
    [c ; k_R'] = a W_DKV;  c = RMSNorm_kv(c);  k_R = RoPE(k_R');  [k_N,i ; v_i] = c W_UKV,i
    k_i = [k_N,i ; k_R];  q_i = [q_N,i ; RoPE(q_R,i)]
    o_i = softmax_{j <= i}(q_i k_i^T / sqrt(qk_nope + qk_rope)) v_i;  h = h + [o_1 .. o_H] W_O
    m = RMSNorm_post(h)
    f = SwiGLU(m) | SwiGLU_shared(m) + sum_{e in top-k of (s + b)} g_e SwiGLU_e(m),
        s = sigmoid(m W_r),  g = routed_scaling_factor x s[chosen] / sum s[chosen]
    h = h + f;  logits = RMSNorm_final(h) W_head
    loss = CE + aux_loss_alpha x mean over sequences of sum over MoE layers of sum_e f_e P_e,
        f_e = E / (k T) #{t: e chosen},  P_e = 1/T sum_t s_e,t / sum_j s_j,t  over a sequence's T non-PAD positions

RoPE turns each pair of interleaved channels (x_2i, x_2i+1) in place by
position x theta^(-2i / qk_rope) — the published modelling code's layout,
written as the pairs' rotation.

Departures from what config.json states, all in the configuration's ``assumed``:

- the balance loss's weight aux_loss_alpha = 1e-4 (section 4.2 of the paper;
  config.json gives ``seq_aux`` and no weight); f counts the experts chosen
  (the top-k of s + b);
- the selection bias takes no gradient; after a step ``b <- b + 0.001 x
  sign(mean(n) - n_e)``, ``n_e`` the step's positions (``PAD`` ones too)
  whose top-k holds expert e, over ALL experts; not centred;
- the *share*: this rank holds experts ``lo .. lo+held`` and a slice of the
  vocabulary; the router keeps every expert's output, the weights are
  normalised over all top-k, and the routed sum runs over the held experts
  only: what the absent ones would add is left out; attention, the shared
  experts and the norms are whole;
- the initial parameters, the tokenisation, the packing and the loss, as
  ``reference/afmoe.py`` has them.

Independent of ``shifu_tpu``: parameters come in as a nested dict of arrays
under the names the saved tower uses; the shapes are this file's own; the
token ids, the split, the order of an epoch's rows, the packing, Adam's first
step and the bias rule are restated in ``reference/afmoe.py`` (and the files
it imports), which this file imports; the count bounds for the bias's judge
are ``reference/lfm2_moe.py``'s.

Controls, for the harness to put through the cell's limits: ``lower=True`` (the
same mathematics in bfloat16) and, as keys of ``cfg``: ``capacity_factor``
(dropped pairs), ``scale_nope`` (the scores scaled by 1 / sqrt(qk_nope)),
``no_kv_norm`` (the latent used without its RMSNorm), ``no_balance`` (the
balance loss left out), ``segment`` (rows not packed: a position sees its own
row's keys only and positions restart with the row).
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .afmoe import (HEAD_CHUNK, QUERY_BLOCK, _cast, _precision, _rms, _swiglu,  # noqa: F401
                    adam_first_step, allowed, bias_after, epoch_order, flatten, nest, pack,
                    rows_to_ids, special_ids, split_rows, ADAM_B1, ADAM_B2)
from .lfm2_moe import count_bounds_of

FAULTS = ("capacity_factor", "scale_nope", "no_kv_norm", "no_balance", "segment")


# ---------------------------------------------------- what the seed decides
def _routed(cfg) -> int:
    return int(cfg["n_routed_experts"]) * int(cfg.get("expert_parallel_size", 1))


def _eps(cfg) -> float:
    return cfg.get("rms_norm_eps", 1e-6)


def _layer_shapes(layer: int, cfg) -> Dict[str, tuple]:
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    out = {"norm_in": (d,), "norm_post": (d,), "wq": (d, h * (nope + rope)),
           "w_dkv": (d, r + rope), "norm_kv": (r,), "w_ukv": (r, h * (nope + dv)), "wo": (h * dv, d)}
    if layer < cfg["first_k_dense_replace"]:
        f = cfg["intermediate_size"]
        return {**out, "w_gate_up": (d, 2 * f), "w_down": (f, d)}
    f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    shared = cfg["n_shared_experts"] * f
    return {**out, "router": (d, _routed(cfg)), "bias": (_routed(cfg),),
            "ws_gate_up": (d, 2 * shared), "ws_down": (shared, d),
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
def knobs_for(cfg, seq: int, rows: int = 1) -> Dict[str, np.ndarray]:
    """What the layers read beside the weights, as data (so that every control
    runs the program the sound configuration runs): the attention mask [S, S],
    the rotary tables [S, qk_rope / 2], the scores' scale, whether the latent
    norm and the balance loss are there, and how many pairs a held expert
    takes of ``rows`` sequences (no limit unless ``capacity_factor``)."""
    rope, theta = cfg["qk_rope_head_dim"], float(cfg.get("rope_theta", 10000.0))
    segment = cfg.get("segment")
    pos = (np.arange(seq) % int(segment) if segment else np.arange(seq)).astype(np.float32)
    inv = 1.0 / (theta ** (np.arange(0, rope, 2, dtype=np.float32) / rope))
    ang = pos[:, None] * inv[None, :]
    width = cfg["qk_nope_head_dim"] + (0 if cfg.get("scale_nope") else rope)
    cap = 2 ** 30
    if cfg.get("capacity_factor"):
        cap = int(np.ceil(cfg["capacity_factor"] * rows * seq * cfg["num_experts_per_tok"]
                          / _routed(cfg)))
    flag = lambda off: np.float32(0.0 if cfg.get(off) else 1.0)
    return {"mask": allowed(seq, None, segment), "cos": np.cos(ang).astype(np.float32),
            "sin": np.sin(ang).astype(np.float32), "scale": np.float32(1.0 / np.sqrt(width)),
            "kv_norm": flag("no_kv_norm"), "balance": flag("no_balance"), "cap": np.int32(cap)}


def rope_pairs(x, cos, sin):
    """x [n, S, ..., c]: each pair (x_2i, x_2i+1) turned in place by the
    angle of the tables' column i ([S, c / 2])."""
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (cos.shape[-1],)
    cos, sin = cos.reshape(shape).astype(x.dtype), sin.reshape(shape).astype(x.dtype)
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    x0, x1 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], -1).reshape(x.shape)


def mla(p, a, cfg, mask, cos, sin, scale, kv_norm):
    """a [n, S, D] (normed) -> [n, S, D]: latent attention under ``mask``
    [S, S]; every head's key is [k_N ; k_R], k_R given to each head."""
    n, s, _ = a.shape
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q = (a @ p["wq"]).reshape(n, s, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], rope_pairs(q[..., nope:], cos, sin)], -1)
    ckr = a @ p["w_dkv"]
    c = jnp.where(kv_norm > 0, _rms(ckr[..., :r], p["norm_kv"], _eps(cfg)), ckr[..., :r])
    k_r = rope_pairs(ckr[..., r:], cos, sin)                           # [n, S, rope]: one a position
    kv = (c @ p["w_ukv"]).reshape(n, s, h, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.stack([k_r] * h, axis=2)], -1)
    v = kv[..., nope:]

    @jax.checkpoint
    def block(qb, ok):
        scores = jnp.einsum("nqhd,nkhd->nhqk", qb, k).astype(jnp.float32) * scale
        probs = jax.nn.softmax(jnp.where(ok[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("nhqk,nkhd->nqhd", probs.astype(v.dtype), v)
    bq = min(QUERY_BLOCK, s)                    # a block of queries at a time, one after the other
    pad = -s % bq
    qs = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(n, -1, bq, h, nope + rope).swapaxes(0, 1)
    oks = jnp.pad(mask, ((0, pad), (0, 0)), constant_values=True).reshape(-1, bq, s)
    o = jax.lax.map(lambda x: block(*x), (qs, oks)).swapaxes(0, 1).reshape(n, -1, h * dv)[:, :s]
    return o @ p["wo"]


def route(p, x, cfg):
    """x [..., D] -> (weights [..., E] of the chosen experts, 0 elsewhere;
    chosen [..., E] bool; the scores s [..., E]) over ALL experts."""
    k, e = cfg["num_experts_per_tok"], p["router"].shape[1]
    s = jax.nn.sigmoid((x @ p["router"]).astype(jnp.float32))
    _, top_e = jax.lax.top_k(s + p["bias"].astype(jnp.float32), k)
    chosen = (top_e[..., None] == jnp.arange(e)).any(-2)
    top_s = jnp.where(chosen, s, 0.0)
    if cfg.get("norm_topk_prob", True):
        top_s = top_s / top_s.sum(-1, keepdims=True)
    return top_s * np.float32(cfg.get("routed_scaling_factor", 1.0)), chosen, s


def balance_of(chosen, s, live, cfg):
    """[n]: each sequence's sum_e f_e P_e over its ``live`` [n, S] positions."""
    e, k = s.shape[-1], cfg["num_experts_per_tok"]
    on = live[..., None].astype(jnp.float32)
    t = jnp.maximum(on.sum(1), 1.0)
    f = (chosen.astype(jnp.float32) * on).sum(1) * (e / k) / t
    big_p = ((s / s.sum(-1, keepdims=True)) * on).sum(1) / t
    return (f * big_p).sum(-1)


def moe_ffn(p, x, cfg, lo: int, live, cap=2 ** 30):
    """(the shared experts + the held experts ``lo .. lo+held`` applied
    densely and weighted; tokens [E]: the positions whose top-k holds each
    expert; the sequences' sum_e f_e P_e [n]).  A held expert takes its
    first ``cap`` pairs."""
    held = p["we_gate_up"].shape[0]
    w_all, chosen, s = route(p, x, cfg)
    w_e = w_all[..., lo:lo + held]
    took = (w_e > 0).reshape(-1, held)
    w_e = jnp.where((jnp.cumsum(took, 0) <= cap).reshape(w_e.shape), w_e, 0.0)
    y = _swiglu(x, p["ws_gate_up"], p["ws_down"])

    def one(y, e):                              # every held expert on every position, in turn
        w_gu, w_d, w = e
        return y + w[..., None].astype(x.dtype) * _swiglu(x, w_gu, w_d), None
    y, _ = jax.lax.scan(jax.checkpoint(one), y, (p["we_gate_up"], p["we_down"], jnp.moveaxis(w_e, -1, 0)))
    return (y, chosen.reshape(-1, chosen.shape[-1]).sum(0).astype(jnp.float32),
            balance_of(chosen, s, live, cfg))


def trunk(params, ids, cfg, lo: int, live=None, knobs=None):
    """ids [n, S] -> (the last layer's output [n, S, D] before ``norm_f``,
    tokens [MoE layers, E], balance [MoE layers, n]); ``live`` [n, S]: the
    positions the balance loss counts (all when None); ``knobs``:
    :func:`knobs_for`'s (``cfg``'s own when None); with a knob ``delta``,
    tokens are ``count_bounds_of``'s [MoE layers, 3, E]."""
    eps = _eps(cfg)
    kn = knobs_for(cfg, ids.shape[1], ids.shape[0]) if knobs is None else knobs
    live = jnp.ones(ids.shape, bool) if live is None else live

    def layer(i):
        @jax.checkpoint
        def fn(h, p, mask, cos, sin, scale, kv_norm, delta):
            h = h + mla(p, _rms(h, p["norm_in"], eps), cfg, mask, cos, sin, scale, kv_norm)
            m = _rms(h, p["norm_post"], eps)
            if i < cfg["first_k_dense_replace"]:
                return h + _swiglu(m, p["w_gate_up"], p["w_down"]), None
            f, tokens, balance = moe_ffn(p, m, cfg, lo, live, kn["cap"])
            if delta is not None:
                tokens = count_bounds_of(p, m, cfg, delta)
            return h + f, (tokens, balance)
        return fn
    h = params["embed"][ids]
    found = []
    data = [jnp.asarray(kn[k]) for k in ("mask", "cos", "sin", "scale", "kv_norm")] + [kn.get("delta")]
    for i, name in enumerate(sorted(params["blocks"])):
        h, out = layer(i)(h, params["blocks"][name], *data)
        if out is not None:
            found.append(out)
    return h, jnp.stack([t for t, _ in found]), jnp.stack([b for _, b in found])


def sequence_loss(params, ids, weights, pad_id, cfg, lo: int, knobs):
    """(sum over targets of weight x CE(logits_i, id_{i+1}), (the weights'
    sum, tokens [MoE layers, E], the sequences' balance sum [MoE layers, n]));
    a target is every non-``PAD`` id but the first."""
    live = (ids != pad_id) & (weights > 0)
    h, tokens, balance = trunk(params, ids, cfg, lo, live, knobs)
    hidden = _rms(h[:, :-1], params["norm_f"], _eps(cfg))
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
    return jnp.sum(jax.lax.map(chunk, (cut(hidden), cut(targets), cut(w)))), (jnp.sum(w), tokens, balance)


# --------------------------------------------------------------- the checks
def loss_and_grads(params, ids: np.ndarray, weights: np.ndarray, pad_id: int, cfg, lo: int,
                   lower: bool = False) -> Tuple[float, dict, np.ndarray, float]:
    """The microbatch's loss over its packed sequences (ids, weights [n, L]):
    the weighted mean cross-entropy + aux_loss_alpha x the mean over the
    sequences with a position that counts of their balance sums; its gradient
    for every parameter, tokens [MoE layers, E] (the microbatch's counts) and
    the balance sum over the MoE layers and sequences, unscaled; one sequence
    at a time."""
    params = _cast(params, lower)
    clean = {k: v for k, v in cfg.items() if k not in FAULTS}       # the faults go in as data
    knobs = jax.tree_util.tree_map(jnp.asarray, knobs_for(cfg, ids.shape[1]))
    targets = (ids[:, 1:] != pad_id) * weights[:, 1:]
    count = float(targets.sum())
    sequences = max(float(((ids != pad_id) & (weights > 0)).any(1).sum()), 1.0)
    alpha = float(cfg.get("aux_loss_alpha", 1e-4))

    def one(p, a, w, pad, kn):
        ce, (c, tokens, balance) = sequence_loss(p, a, w, pad, clean, lo, kn)
        bal = jnp.sum(balance) * kn["balance"]
        return ce / count + alpha * bal / sequences, (tokens, bal)
    fn = jax.jit(jax.value_and_grad(one, has_aux=True))
    total = balance = 0.0
    grads = tokens = None
    with _precision(lower):
        for a in range(len(ids)):
            (l, (t, b)), g = fn(params, jnp.asarray(ids[a:a + 1], jnp.int32),
                                jnp.asarray(weights[a:a + 1], jnp.float32), jnp.int32(pad_id), knobs)
            total, balance = total + float(l), balance + float(b)
            # summed on the host: the device holds one sequence's gradients, never two
            g = jax.tree_util.tree_map(lambda v: np.asarray(v.astype(jnp.float32)), g)
            grads = g if grads is None else jax.tree_util.tree_map(np.add, grads, g)
            tokens = np.asarray(t) if tokens is None else tokens + np.asarray(t)
            del g
    return total, grads, tokens, balance


def count_bounds(params, ids: np.ndarray, cfg, lo: int, delta: float) -> np.ndarray:
    """[MoE layers, 3, E] of the microbatch's packed sequences ``ids`` [n, L]:
    each expert's count, and the fewest and the most a router could count
    whose every biased score lies within ``delta`` / 2 of this one's
    (``reference/lfm2_moe.count_bounds_of``), one sequence at a time."""
    params = _cast(params, False)
    clean = {k: v for k, v in cfg.items() if k not in FAULTS}
    fn = jax.jit(lambda p, a, kn: trunk(p, a, clean, lo, None, kn)[1])
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
        h = _rms(trunk(p, a, clean, lo, None, kn)[0][:, -1], p["norm_f"], _eps(cfg))
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
        h, _, _ = trunk(p, jnp.asarray(ids, jnp.int32), cfg, lo)
        return np.asarray(_rms(h, p["norm_f"], _eps(cfg)) @ p["head"])
