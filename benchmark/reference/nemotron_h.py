"""Plain reference of the Nemotron-H tower over tokenised rows: Mamba-2 mixers,
causal grouped-query attention, LatentMoE feed-forwards and one multi-token
prediction module; forward, next-token loss, gradients and Adam's first step in
straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``.  The recurrence runs position by
position, every held expert is applied densely to every position in a loop, no
kernels, no chunks, no recomputation.  Computed in blocks of rows so that the
published widths fit beside nothing else.

Follows config.json of nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
(``nemotron_h``): one mixer a layer after ``hybrid_override_pattern``
(``M`` Mamba-2, ``*`` attention, ``E`` LatentMoE), ``h <- h + Mixer(RMSNorm(h))``,
a final RMSNorm, an untied head; the MTP module after Megatron-Core's
``MultiTokenPredictionLayer``.  Departures from the published description, all
in the configuration's ``assumed``:

- attention applies no rotary embedding (``nemotron_h``'s does not, although
  config.json carries ``rope_theta``);
- the router's selection bias takes no gradient and no update rule;
- the MTP wiring (``[RMSNorm(h_i) ; RMSNorm(Embed[id_{i+1}])] W_eh``, its own
  layers and final norm, the shared embedding and head, target ``id_{i+2}``)
  and its loss weight 0.1 are Megatron-Core's, not config.json's;
- the initial parameters, the loss (a row ``[f_0 .. f_{C-1}, TAG]`` is a
  sequence, ``S_0 = 0`` at its first position) and the tokenisation;
- the *share*: this rank holds Mamba heads, their one B/C group, query heads
  with their key-value head, experts ``lo .. lo+held`` and a slice of the
  vocabulary.  ``w_out`` / ``wo`` give the rank's partial sum; the routed sum
  runs over the chosen experts that are held, with the weights normalised over
  all top-k; what the absent ranks would add is left out.

Independent of ``shifu_tpu``: parameters come in as a nested dict of arrays
under the names the saved tower uses; the token ids, the split, the order of
an epoch's rows, the initial parameters and Adam's first step are restated
(those the two towers share, in ``reference/sdar_moe.py``).

Controls, for the driver to put through its own limits: ``lower=True`` (the
same mathematics in bfloat16: parameters, activations, state, softmax, router,
loss, optimizer) and, as keys of ``cfg``: ``capacity_factor`` (a held expert
takes the first ``factor x tokens x k / experts`` pairs of a row block, the
later ones are dropped), ``reset_state_every`` (the recurrent state zeroed at
every multiple of that many positions: a chunked scan that loses its carry),
``no_mtp`` (the MTP term left out of the loss), ``softmax_router``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .sdar_moe import (ADAM_B1, ADAM_B2, adam_first_step, epoch_order,  # noqa: F401
                       special_ids, split_rows, token_offsets)

MTP_LOSS_SCALE = 0.1


# ------------------------------------------------------------- tokens, rows
def rows_to_ids(bins: np.ndarray, y: np.ndarray, column_bins) -> np.ndarray:
    """[n, C + 1] ids: one token a column, then ``TAG_y``."""
    sp = special_ids(column_bins)
    ids = np.empty((bins.shape[0], bins.shape[1] + 1), np.int64)
    ids[:, :-1] = bins.astype(np.int64) + token_offsets(column_bins)[None, :]
    ids[:, -1] = np.where(np.asarray(y) > 0.5, sp["TAG1"], sp["TAG0"])
    return ids


# ---------------------------------------------------- what the seed decides
def _layer_shapes(kind: str, cfg) -> Dict[str, tuple]:
    d = cfg["hidden_size"]
    if kind == "M":
        h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
        gn = cfg["n_groups"] * cfg["ssm_state_size"]
        di = h * p
        return {"norm": (d,), "w_in": (d, 2 * di + 2 * gn + h),
                "conv_w": (cfg["conv_kernel"], di + 2 * gn), "conv_b": (di + 2 * gn,),
                "dt_bias": (h,), "A_log": (h,), "D": (h,), "w_norm": (di,), "w_out": (di, d)}
    if kind == "*":
        h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
        return {"norm": (d,), "wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
                "wo": (h * hd, d)}
    held, lat = cfg["n_routed_experts"], cfg["moe_latent_size"]
    f, fs = cfg["moe_intermediate_size"], cfg["moe_shared_expert_intermediate_size"]
    routed = held * int(cfg.get("expert_parallel_size", 1))
    return {"norm": (d,), "router": (d, routed), "bias": (routed,), "w_lat1": (d, lat),
            "w_lat2": (lat, d), "w_up": (held, lat, f), "w_down": (held, f, lat),
            "ws_up": (d, fs), "ws_down": (fs, d)}


def param_shapes(cfg) -> Dict[str, tuple]:
    """Flat name -> shape of every array, as the share has them."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": (v, d), "head": (d, v), "norm_f": (d,)}
    for i, c in enumerate(cfg["hybrid_override_pattern"]):
        out.update({f"blocks.{i:02d}.{k}": s for k, s in _layer_shapes(c, cfg).items()})
    if int(cfg.get("num_nextn_predict_layers", 0)):
        out.update({"mtp.norm_h": (d,), "mtp.norm_e": (d,), "mtp.w_eh": (2 * d, d),
                    "mtp.norm_f": (d,)})
        for i, c in enumerate(cfg["mtp_hybrid_override_pattern"]):
            out.update({f"mtp.blocks.{i}.{k}": s for k, s in _layer_shapes(c, cfg).items()})
    return out


def _draw(key, name: str, shape, cfg):
    leaf = name.rsplit(".", 1)[-1]
    u = lambda lo, hi: jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if leaf.startswith("norm") or leaf in ("w_norm", "D"):
        return jnp.ones(shape, jnp.float32)
    if leaf == "bias":
        return jnp.zeros(shape, jnp.float32)
    if leaf == "A_log":
        return jnp.log(u(1.0, 16.0))
    if leaf == "dt_bias":
        lo, hi = np.log(cfg.get("time_step_min", 0.001)), np.log(cfg.get("time_step_max", 0.1))
        dt = jnp.maximum(jnp.exp(u(lo, hi)), cfg.get("time_step_floor", 1e-4))
        return dt + jnp.log(-jnp.expm1(-dt))                 # softplus^-1
    if leaf in ("conv_w", "conv_b"):
        bound = 1.0 / np.sqrt(cfg["conv_kernel"])
        return u(-bound, bound)
    return 0.02 * jax.random.normal(key, shape, jnp.float32)


def nest(flat: dict) -> dict:
    out: dict = {}
    for name, a in flat.items():
        node = out
        *path, leaf = name.split(".")
        for part in path:
            node = node.setdefault(part, {})
        node[leaf] = a
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        out.update(flatten(v, prefix + k + ".") if isinstance(v, dict) else {prefix + k: v})
    return out


def init_params(seed: int, cfg) -> dict:
    """What a fresh job starts from (the configuration's ``assumed.init``):
    array ``i`` of the names in sorted order is drawn from ``fold_in(key,
    i)``: normal(0, 0.02) matrices; unit norm weights and ``D``; a zero
    selection bias; ``A_log`` = log U[1, 16]; ``dt_bias`` = softplus^-1 of a
    log-uniform step in [time_step_min, time_step_max], floored; the
    depthwise conv's weight and bias U(-1/sqrt(k), 1/sqrt(k))."""
    key = jax.random.PRNGKey(seed)
    shapes = param_shapes(cfg)
    return nest({name: np.asarray(_draw(jax.random.fold_in(key, i), name, shapes[name], cfg))
                 for i, name in enumerate(sorted(shapes))})


# ------------------------------------------------------------------ mixers
def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)).astype(x.dtype) * w


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def mamba_mixer(p, x, cfg):
    """x [n, T, D] -> this rank's partial sum [n, T, D].  The recurrence
    ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``
    position by position, ``S = 0`` before a row's first position."""
    n, t, _ = x.shape
    h, pd, g, ns = (cfg[k] for k in ("mamba_num_heads", "mamba_head_dim", "n_groups",
                                     "ssm_state_size"))
    di, k = h * pd, cfg["conv_kernel"]
    zxbcdt = x @ p["w_in"]
    z, xbc, dt = zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * g * ns], zxbcdt[..., 2 * di + 2 * g * ns:]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, j:j + t] * p["conv_w"][j] for j in range(k)) + p["conv_b"])
    xs = xbc[..., :di].reshape(n, t, h, pd)
    b = jnp.repeat(xbc[..., di:di + g * ns].reshape(n, t, g, ns), h // g, axis=2)
    c = jnp.repeat(xbc[..., di + g * ns:].reshape(n, t, g, ns), h // g, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                               # [n, T, H]
    a = -jnp.exp(p["A_log"])
    every = int(cfg.get("reset_state_every") or 0)
    keep = jnp.asarray([0.0 if every and i % every == 0 else 1.0 for i in range(t)], x.dtype)

    def step(s, inp):
        x_t, b_t, c_t, dt_t, keep_t = inp
        s = jnp.exp(dt_t * a)[..., None, None] * (keep_t * s) + \
            (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.einsum("nhps,nhs->nhp", s, c_t) + p["D"][:, None] * x_t

    s0 = jnp.zeros((n, h, pd, ns), x.dtype)
    _, y = jax.lax.scan(step, s0, (xs.swapaxes(0, 1), b.swapaxes(0, 1), c.swapaxes(0, 1),
                                   dt.swapaxes(0, 1), keep))
    y = y.swapaxes(0, 1).reshape(n, t, di) * jax.nn.silu(z)
    y = _rms(y.reshape(n, t, g, di // g), 1.0, cfg["layer_norm_epsilon"]).reshape(n, t, di)
    return (y * p["w_norm"]) @ p["w_out"]


def attention_mixer(p, x, cfg):
    """Causal grouped-query attention, no rotary; this rank's heads."""
    n, t, _ = x.shape
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (x @ p["wq"]).reshape(n, t, h, hd)
    k = jnp.repeat((x @ p["wk"]).reshape(n, t, kv, hd), h // kv, axis=2)
    v = jnp.repeat((x @ p["wv"]).reshape(n, t, kv, hd), h // kv, axis=2)
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k) / np.sqrt(hd).astype(np.float32)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("nhqk,nkhd->nqhd", probs, v).reshape(n, t, h * hd) @ p["wo"]


def moe_mixer(p, x, cfg, lo: int):
    """LatentMoE.  Scores ``sigmoid(x W_r)`` over all experts; chosen = the
    top-k of score + selection bias; weights = the chosen scores over their
    sum, times ``routed_scaling_factor``; the held experts ``lo .. lo+held``
    applied densely, in the latent space, and weighted; the shared expert on
    ``x`` itself."""
    k = cfg["num_experts_per_tok"]
    held = p["w_up"].shape[0]
    logits = x @ p["router"]
    s = jax.nn.softmax(logits, -1) if cfg.get("softmax_router") else jax.nn.sigmoid(logits)
    _, top_e = jax.lax.top_k(s + p["bias"], k)
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    if cfg.get("norm_topk_prob", True):
        top_s = top_s / top_s.sum(-1, keepdims=True)
    top_w = top_s * jnp.asarray(cfg.get("routed_scaling_factor", 1.0), s.dtype)
    w_e = (top_w[..., None] * (top_e[..., None] == lo + jnp.arange(held))).sum(-2)   # [..., held]
    if cfg.get("capacity_factor"):
        chosen = (w_e > 0).reshape(-1, held)
        cap = int(np.ceil(cfg["capacity_factor"] * chosen.shape[0] * k / s.shape[-1]))
        w_e = jnp.where((jnp.cumsum(chosen, 0) <= cap).reshape(w_e.shape), w_e, 0.0)
    u = x @ p["w_lat1"]
    y = jnp.zeros_like(u)
    for e in range(held):
        y = y + w_e[..., e:e + 1].astype(u.dtype) * (_relu2(u @ p["w_up"][e]) @ p["w_down"][e])
    return y @ p["w_lat2"] + _relu2(x @ p["ws_up"]) @ p["ws_down"]


def _layers(blocks: dict, pattern: str, h, cfg, lo: int):
    for c, name in zip(pattern, sorted(blocks)):
        p = blocks[name]
        x = _rms(h, p["norm"], cfg["layer_norm_epsilon"])
        h = h + (mamba_mixer(p, x, cfg) if c == "M" else
                 attention_mixer(p, x, cfg) if c == "*" else moe_mixer(p, x, cfg, lo))
    return h


def trunk(params, ids, cfg, lo: int):
    """ids [n, T] -> the last layer's output [n, T, D], before ``norm_f``."""
    return _layers(params["blocks"], cfg["hybrid_override_pattern"], params["embed"][ids], cfg, lo)


def mtp_hidden(params, h, next_ids, cfg, lo: int):
    """h [n, T, D] the trunk's output at positions i, next_ids [n, T] the ids
    at i + 1 -> the module's final-normed hidden state, which predicts i + 2."""
    m, eps = params["mtp"], cfg["layer_norm_epsilon"]
    x = jnp.concatenate([_rms(h, m["norm_h"], eps),
                         _rms(params["embed"][next_ids], m["norm_e"], eps)], -1) @ m["w_eh"]
    return _rms(_layers(m["blocks"], cfg["mtp_hybrid_override_pattern"], x, cfg, lo),
                m["norm_f"], eps)


def _ce(hidden, head, targets):
    logits = (hidden @ head).astype(jnp.float32)
    return jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]


def row_losses(params, ids, cfg, lo: int):
    """Per row: mean over i of CE(logits_i, id_{i+1}) + 0.1 x mean over i of
    CE(mtp logits_i, id_{i+2})."""
    h = trunk(params, ids[:, :-1], cfg, lo)
    loss = _ce(_rms(h, params["norm_f"], cfg["layer_norm_epsilon"]), params["head"],
               ids[:, 1:]).mean(-1)
    if int(cfg.get("num_nextn_predict_layers", 0)) and not cfg.get("no_mtp"):
        hm = mtp_hidden(params, h[:, :-1], ids[:, 1:-1], cfg, lo)
        loss = loss + MTP_LOSS_SCALE * _ce(hm, params["head"], ids[:, 2:]).mean(-1)
    return loss


# --------------------------------------------------------------- the checks
def _cast(params, lower: bool):
    dt = jnp.bfloat16 if lower else jnp.float32
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a).astype(dt), params)


def _precision(lower: bool):
    return jax.default_matmul_precision("default" if lower else "highest")


def loss_and_grads(params, ids: np.ndarray, cfg, lo: int, rows_per_block: int = 1,
                   lower: bool = False, weights=None) -> Tuple[float, dict]:
    """The microbatch's loss (the rows' weighted mean of :func:`row_losses`)
    and its gradient for every parameter, summed over blocks of rows."""
    params = _cast(params, lower)
    w = np.ones(len(ids), np.float32) if weights is None else np.asarray(weights, np.float32)
    fn = jax.jit(jax.value_and_grad(
        lambda p, a, wb: jnp.sum(row_losses(p, a, cfg, lo).astype(jnp.float32) * wb)))
    ids = jnp.asarray(ids, jnp.int32)
    total, grads = 0.0, None
    with _precision(lower):
        for a in range(0, ids.shape[0], rows_per_block):
            sl = slice(a, a + rows_per_block)
            l, g = fn(params, ids[sl], jnp.asarray(w[sl]))
            total += float(l)
            # summed on the host: the device holds one block's gradients, never two
            g = jax.tree_util.tree_map(lambda v: np.asarray(v.astype(jnp.float32)), g)
            grads = g if grads is None else jax.tree_util.tree_map(np.add, grads, g)
            del g
    count = float(w.sum())
    return total / count, jax.tree_util.tree_map(lambda v: v / np.float32(count), grads)


def tag_logit_difference(params, bins: np.ndarray, cfg, lo: int, column_bins,
                         rows_per_block: int = 16, lower: bool = False) -> np.ndarray:
    """``eval``'s quantity for each row: one causal forward over the feature
    tokens, logit_TAG1 - logit_TAG0 at the last of them (the score is 1000
    sigmoid of it); no MTP."""
    sp = special_ids(column_bins)
    ids = rows_to_ids(bins, np.zeros(len(bins)), column_bins)[:, :-1]
    params = _cast(params, lower)

    @jax.jit
    def fn(p, a, tag0):
        h = _rms(trunk(p, a, cfg, lo)[:, -1], p["norm_f"], cfg["layer_norm_epsilon"])
        two = (h @ jax.lax.dynamic_slice_in_dim(p["head"], tag0, 2, axis=1)).astype(jnp.float32)
        return two[:, 1] - two[:, 0]
    out = []
    with _precision(lower):
        for a in range(0, len(ids), rows_per_block):
            out.append(np.asarray(fn(params, jnp.asarray(ids[a: a + rows_per_block], jnp.int32),
                                     jnp.int32(sp["TAG0"]))))
    return np.concatenate(out)


def forward_logits(params, ids: np.ndarray, cfg, lo: int) -> Tuple[np.ndarray, np.ndarray]:
    """(next-token logits [n, T-1, V], MTP logits [n, T-2, V]): the tests' comparison."""
    with _precision(False):
        p, ids = _cast(params, False), jnp.asarray(ids, jnp.int32)
        h = trunk(p, ids[:, :-1], cfg, lo)
        main = _rms(h, p["norm_f"], cfg["layer_norm_epsilon"]) @ p["head"]
        return np.asarray(main), np.asarray(mtp_hidden(p, h[:, :-1], ids[:, 1:-1], cfg, lo) @ p["head"])
