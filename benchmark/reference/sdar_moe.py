"""Plain reference of the SDAR block-diffusion mixture-of-experts tower over
tokenised rows: forward, loss and gradients in straightforward ``jax.numpy``,
float32, under ``jax.default_matmul_precision("highest")``.  No sort, no
kernels, no recomputation: every held expert is applied densely to every
position and weighted by the router.  Computed in blocks of rows so that the
published widths fit beside nothing else.

Follows config.json of JetLM/SDAR-30B-A3B-Chat (``sdar_moe``: RMSNorm, GQA with
per-head q/k RMSNorm, RoPE rotate-half, softmax router over all experts with
the top-k renormalised, SwiGLU experts, untied head) and, for training, Block
Diffusion (Arriola et al., ICLR 2025).  Departures, all stated in the
configuration's ``assumed``: block length, noise schedule and loss weight are
not in config.json; the *share* — this rank holds experts ``lo .. lo+held``,
the sum runs over the chosen experts that are held, with the weights
normalised over all top-k, and what the absent experts would add is left out.

Independent of ``shifu_tpu``: the parameters come in as a dict of arrays under
the names the saved tower uses, everything else is restated here — the token
ids, the row layout, the masks, the split, the order of an epoch's rows, the
noise and the initial parameters drawn from the seed, and Adam's first step.

Two controls, for the drivers to put through their own limits
(``drivers/train_tower.py`` ``controls``; which limit refuses which is a
reading, in ``PERF.md``).  ``lower=True`` computes the same mathematics one
precision below what the configuration states: bfloat16 parameters,
activations, softmax, router, loss and optimizer state (XLA's dots still
accumulate in f32).  ``cfg["capacity_factor"]`` plants dropped pairs: a held
expert takes the first ``factor x tokens x k / experts`` of a row block's
pairs and the later ones are left out, as a dispatch with a capacity does.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

T_MIN = 1e-3                    # per block t ~ U(T_MIN, 1]
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


# ------------------------------------------------------------- tokens, rows
def token_offsets(column_bins) -> np.ndarray:
    """First id of each column: a column with ``b`` value bins owns ``b + 1``
    ids (the last is its missing bin), in the plane's column order."""
    sizes = np.asarray(column_bins, np.int64) + 1
    return np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int64)


def special_ids(column_bins) -> Dict[str, int]:
    base = int((np.asarray(column_bins, np.int64) + 1).sum())
    return {"TAG0": base, "TAG1": base + 1, "MASK": base + 2, "PAD": base + 3}


def rows_to_ids(bins: np.ndarray, y: np.ndarray, column_bins, block: int) -> np.ndarray:
    """[n, S] ids: one token a column (padded with PAD to whole blocks), then
    one block ``[TAG_y, PAD, ...]``."""
    sp = special_ids(column_bins)
    n, c = bins.shape
    feat = -(-c // block) * block
    ids = np.full((n, feat + block), sp["PAD"], np.int64)
    ids[:, :c] = bins.astype(np.int64) + token_offsets(column_bins)[None, :]
    ids[:, feat] = np.where(np.asarray(y) > 0.5, sp["TAG1"], sp["TAG0"])
    return ids


# ----------------------------------------------------------------- the mask
def block_mask(s: int, block: int) -> np.ndarray:
    """[2S, 2S] bool over ``[x_t ; x_0]``, True = the query (row) sees the key
    (column).  A noised query sees noised keys of its own block and clean keys
    of earlier blocks; a clean query sees clean keys of its own and earlier
    blocks; nothing else."""
    blk = np.arange(s) // block
    same, earlier = blk[:, None] == blk[None, :], blk[None, :] < blk[:, None]
    top = np.concatenate([same, earlier], 1)
    bottom = np.concatenate([np.zeros((s, s), bool), same | earlier], 1)
    return np.concatenate([top, bottom], 0)


def eval_mask(s: int, block: int) -> np.ndarray:
    """[S, S] bool: block-causal, bidirectional inside a block."""
    blk = np.arange(s) // block
    return blk[None, :] <= blk[:, None]


# ---------------------------------------------------- what the seed decides
def split_rows(n: int, valid_rate: float, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(training rows, validation rows) of the plane, both ascending: the
    first ``round(n x valid_rate)`` of a seeded permutation validate."""
    perm = np.random.default_rng(seed).permutation(n)
    n_valid = int(round(n * valid_rate))
    return np.sort(perm[n_valid:]), np.sort(perm[:n_valid])


def init_params(seed: int, cfg) -> dict:
    """What a fresh job starts from (the configuration's ``assumed.init``):
    normal(0, 0.02) matrices, one split of the seed's key each in this order,
    layers stacked on a leading axis; unit norm weights.  ``cfg`` as the
    share has it: ``num_experts`` held of ``x expert_parallel_size`` routed."""
    d, hd, f = cfg["hidden_size"], cfg["head_dim"], cfg["moe_intermediate_size"]
    n, held = cfg["num_hidden_layers"], cfg["num_experts"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    shapes = {"wq": (n, d, h * hd), "wk": (n, d, kv * hd), "wv": (n, d, kv * hd),
              "wo": (n, h * hd, d),
              "router": (n, d, held * int(cfg.get("expert_parallel_size", 1))),
              "w_gate_up": (n, held, d, 2 * f), "w_down": (n, held, f, d),
              "embed": (cfg["vocab_size"], d), "head": (d, cfg["vocab_size"])}
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    mats = {name: np.asarray(0.02 * jax.random.normal(k, shape, jnp.float32))
            for k, (name, shape) in zip(keys, shapes.items())}
    ones = lambda *shape: np.ones(shape, np.float32)
    layers = {k: mats[k] for k in list(shapes)[:7]}
    layers.update(ln1=ones(n, d), ln2=ones(n, d), q_norm=ones(n, hd), k_norm=ones(n, hd))
    return {"embed": mats["embed"], "layers": layers, "final_norm": ones(d), "head": mats["head"]}


def adam_first_step(before, grad, lr: float, lower: bool = False):
    """(m, v, the parameter after) of Adam's first step from zero moments on
    one array: m = (1 - b1) g, v = (1 - b2) g^2, the step -lr m^ / (sqrt(v^)
    + eps) with both bias corrections.  ``lower``: stored and computed in
    bfloat16."""
    return tuple(np.asarray(a.astype(jnp.float32)) for a in _adam_first_step(
        jnp.asarray(before), jnp.asarray(grad), jnp.float32(lr), lower))


@partial(jax.jit, static_argnames=("lower",))
def _adam_first_step(p, g, lr, lower):
    dt = jnp.bfloat16 if lower else jnp.float32
    p, g, lr = p.astype(dt), g.astype(dt), lr.astype(dt)
    m, v = (1 - ADAM_B1) * g, (1 - ADAM_B2) * g * g
    step = lr * (m / (1 - ADAM_B1)) / (jnp.sqrt(v / (1 - ADAM_B2)) + ADAM_EPS)
    return m, v, p - step            # leave in their storage type: the rounding is the control


def epoch_order(seed: int, epoch: int, n_train: int) -> np.ndarray:
    """The order in which an epoch visits the training rows."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), epoch), 0)
    return np.asarray(jax.random.permutation(key, n_train))


def noise(seed: int, epoch: int, step: int, rows: int, s: int, block: int):
    """(t [rows, S], masked [rows, S]) of one microbatch: per block
    t ~ U(T_MIN, 1], each position masked with probability t."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), epoch), 1 + step)
    kt, km = jax.random.split(key)
    t_blk = jax.random.uniform(kt, (rows, s // block), jnp.float32, T_MIN, 1.0)
    t = jnp.repeat(t_blk, block, axis=1)
    masked = jax.random.uniform(km, (rows, s), jnp.float32) < t
    return t, masked


# ------------------------------------------------------------------ forward
def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)).astype(x.dtype) * w


def _rope(x, pos, theta):
    """x [..., S, heads, hd]; rotate-half over the whole head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :].astype(x.dtype)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(p, x, pos, mask, cfg):
    n, s, _ = x.shape
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (x @ p["wq"]).reshape(n, s, h, hd)
    k = (x @ p["wk"]).reshape(n, s, kv, hd)
    v = (x @ p["wv"]).reshape(n, s, kv, hd)
    q = _rope(_rms(q, p["q_norm"], cfg["rms_norm_eps"]), pos, cfg["rope_theta"])
    k = _rope(_rms(k, p["k_norm"], cfg["rms_norm_eps"]), pos, cfg["rope_theta"])
    k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k) / np.sqrt(hd).astype(np.float32)
    scores = jnp.where(jnp.asarray(mask)[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("nhqk,nkhd->nqhd", probs, v).reshape(n, s, h * hd)
    return out @ p["wo"]


def moe_layer(p, x, cfg, lo: int):
    """x [..., D].  Router over all experts, the top-k renormalised; the
    experts ``lo .. lo+held`` applied densely and weighted; the others' part
    left out.  ``p['w_gate_up']`` is [held, D, 2F]: gate then up.
    ``cfg['capacity_factor']`` (a planted fault, see the module's text) drops
    the pairs past each held expert's capacity, in token order."""
    k = cfg["num_experts_per_tok"]
    held, _, f2 = p["w_gate_up"].shape
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_w = top_p / top_p.sum(-1, keepdims=True) if cfg.get("norm_topk_prob", True) else top_p
    # weight of held expert e for each position: its renormalised top-k weight, else 0
    w_e = (top_w[..., None] * (top_e[..., None] == lo + jnp.arange(held))).sum(-2)
    if cfg.get("capacity_factor"):
        chosen = (w_e > 0).reshape(-1, held)
        cap = int(np.ceil(cfg["capacity_factor"] * chosen.shape[0] * k / probs.shape[-1]))
        w_e = jnp.where((jnp.cumsum(chosen, 0) <= cap).reshape(w_e.shape), w_e, 0.0)
    gu = jnp.einsum("...d,edf->...ef", x, p["w_gate_up"])           # every held expert, every position
    h = jax.nn.silu(gu[..., : f2 // 2]) * gu[..., f2 // 2:]
    y = jnp.einsum("...ef,efd->...ed", h, p["w_down"])
    return jnp.sum(w_e[..., None].astype(x.dtype) * y, axis=-2)


def _layer(p, h, pos, mask, cfg, lo):
    eps = cfg["rms_norm_eps"]
    h = h + _attention(p, _rms(h, p["ln1"], eps), pos, mask, cfg)
    return h + moe_layer(p, _rms(h, p["ln2"], eps), cfg, lo)


def hidden(params, ids, pos, mask, cfg, lo):
    """ids [n, T] -> final-normed hidden [n, T, D]."""
    h = params["embed"][ids]
    n_layers = params["layers"]["wq"].shape[0]
    for i in range(n_layers):
        p = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        h = _layer(p, h, pos, mask, cfg, lo)
    return _rms(h, params["final_norm"], cfg["rms_norm_eps"])


def _cast(params, lower: bool):
    dt = jnp.bfloat16 if lower else jnp.float32
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a).astype(dt), params)


def _precision(lower: bool):
    return jax.default_matmul_precision("default" if lower else "highest")


def train_logits(params, x0, masked, cfg, lo, mask_id, block):
    """Logits [n, S, V] of the noised half of ``[x_t ; x_0]``.  The special
    ids are values, not constants of the compiled program: they follow the
    columns' bins, and every seed's table would compile anew."""
    s = x0.shape[1]
    xt = jnp.where(masked, mask_id, x0)
    ids = jnp.concatenate([xt, x0], 1)
    pos = jnp.concatenate([jnp.arange(s), jnp.arange(s)])
    h = hidden(params, ids, pos, block_mask(s, block), cfg, lo)
    return h[:, :s] @ params["head"]


def loss_sum(params, x0, t, masked, cfg, lo, mask_id, pad_id, block):
    """Sum over masked, non-PAD positions of (1/t) CE(logits_i, x_0,i); the
    caller divides by the count of non-PAD positions."""
    logits = train_logits(params, x0, masked, cfg, lo, mask_id, block).astype(jnp.float32)
    ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(logits, x0[..., None], -1)[..., 0]
    use = masked & (x0 != pad_id)
    return jnp.sum(jnp.where(use, ce / t, 0.0))


def loss_and_grads(params, x0: np.ndarray, t, masked, cfg, lo: int, column_bins, block: int,
                   rows_per_block: int = 2, lower: bool = False) -> Tuple[float, dict]:
    """The microbatch's loss and its gradient for every parameter, summed over
    blocks of ``rows_per_block`` rows and divided by the non-PAD count."""
    sp = special_ids(column_bins)
    params = _cast(params, lower)
    fn = jax.jit(jax.value_and_grad(
        lambda p, a, b, c, m, d: loss_sum(p, a, b, c, cfg, lo, m, d, block).astype(jnp.float32)))
    mask_id, pad_id = jnp.int32(sp["MASK"]), jnp.int32(sp["PAD"])
    x0 = jnp.asarray(x0, jnp.int32)
    total, grads = 0.0, None
    with _precision(lower):
        for a in range(0, x0.shape[0], rows_per_block):
            sl = slice(a, a + rows_per_block)
            l, g = fn(params, x0[sl], t[sl], masked[sl], mask_id, pad_id)
            g = jax.tree_util.tree_map(lambda v: v.astype(jnp.float32), g)
            total += float(l)
            grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
    count = float(np.sum(np.asarray(x0) != sp["PAD"]))
    return total / count, jax.tree_util.tree_map(lambda v: np.asarray(v) / count, grads)


def tag_logit_difference(params, bins: np.ndarray, cfg, lo: int, column_bins, block: int,
                         rows_per_block: int = 16, lower: bool = False) -> np.ndarray:
    """``eval``'s quantity for each row: input ``[features clean ; MASK x B]``
    under the block-causal mask, logit_TAG1 - logit_TAG0 at the tag's position
    (the score is 1000 sigmoid of it)."""
    sp = special_ids(column_bins)
    ids = rows_to_ids(bins, np.zeros(len(bins)), column_bins, block)
    s = ids.shape[1]
    ids[:, s - block:] = sp["MASK"]
    params = _cast(params, lower)

    @jax.jit
    def fn(p, a, tag0):
        h = hidden(p, a, jnp.arange(s), eval_mask(s, block), cfg, lo)
        two = jax.lax.dynamic_slice_in_dim(p["head"], tag0, 2, axis=1)
        two = (h[:, s - block] @ two).astype(jnp.float32)
        return two[:, 1] - two[:, 0]
    out = []
    with _precision(lower):
        for a in range(0, len(ids), rows_per_block):
            out.append(np.asarray(fn(params, jnp.asarray(ids[a: a + rows_per_block], jnp.int32),
                                     jnp.int32(sp["TAG0"]))))
    return np.concatenate(out)


def forward_logits(params, x0: np.ndarray, masked, cfg, lo: int, column_bins, block: int) -> np.ndarray:
    """Training-forward logits of the noised half (the tests' comparison)."""
    with _precision(False):
        return np.asarray(train_logits(_cast(params, False), jnp.asarray(x0, jnp.int32), masked,
                                       cfg, lo, special_ids(column_bins)["MASK"], block))
