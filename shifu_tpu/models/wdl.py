"""Wide-and-deep model — reference ``core/dtrain/wdl/`` (5.7k LoC:
``WideAndDeep.java:50`` layer graph of DenseLayer / EmbedLayer / WideLayer /
BiasLayer) as one jitted forward.

- deep side: per-categorical-column embedding tables (missing bin = one extra
  row) concatenated with the normalized numeric block, through dense layers;
- wide side: per-categorical-column scalar weight per bin (the sparse LR of
  ``WideLayer``) plus a linear term on numerics;
- output: sigmoid(deep + wide + bias), trained with weighted log loss
  (reference wdl worker ``WDLWorker.java:679-712`` fwd/bwd per record — here
  one batched matmul/gather step).

Embedding gathers batch to one ``take`` per column; XLA fuses the concat +
first dense matmul onto the MXU.
"""

from __future__ import annotations

import io
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .. import ioutil

import jax
import jax.numpy as jnp


@dataclass
class WDLModelSpec:
    numeric_dim: int
    cat_cardinalities: List[int]        # bins incl. the missing bin, per col
    embed_dim: int = 8
    hidden_nodes: List[int] = field(default_factory=lambda: [64, 32])
    activations: List[str] = field(default_factory=lambda: ["relu", "relu"])
    wide_enable: bool = True
    deep_enable: bool = True
    column_nums: Optional[List[int]] = None
    cat_column_nums: Optional[List[int]] = None
    extra: Dict[str, Any] = field(default_factory=dict)

    def to_json(self) -> str:
        return json.dumps({
            "version": 1, "kind": "wdl", "numeric_dim": self.numeric_dim,
            "cat_cardinalities": self.cat_cardinalities,
            "embed_dim": self.embed_dim, "hidden_nodes": self.hidden_nodes,
            "activations": self.activations, "wide_enable": self.wide_enable,
            "deep_enable": self.deep_enable, "column_nums": self.column_nums,
            "cat_column_nums": self.cat_column_nums, "extra": self.extra})

    @classmethod
    def from_json(cls, s: str) -> "WDLModelSpec":
        d = json.loads(s)
        return cls(numeric_dim=d["numeric_dim"],
                   cat_cardinalities=d["cat_cardinalities"],
                   embed_dim=d.get("embed_dim", 8),
                   hidden_nodes=d.get("hidden_nodes", [64, 32]),
                   activations=d.get("activations", ["relu", "relu"]),
                   wide_enable=d.get("wide_enable", True),
                   deep_enable=d.get("deep_enable", True),
                   column_nums=d.get("column_nums"),
                   cat_column_nums=d.get("cat_column_nums"),
                   extra=d.get("extra", {}))


def init_params(key, spec: WDLModelSpec) -> Dict:
    from .nn import NNModelSpec, init_params as nn_init
    params: Dict[str, Any] = {}
    n_cat = len(spec.cat_cardinalities)
    keys = jax.random.split(key, n_cat + 2)
    if spec.deep_enable:
        # fan-in scaling: the first dense layer sees embed_dim inputs per
        # column, so variance 1/embed_dim keeps its pre-activations O(1)
        # at any embed_dim/hash-bucket count (a fixed 0.05 degrades as
        # embed_dim grows)
        scale = spec.embed_dim ** -0.5
        params["embed"] = [
            jax.random.normal(keys[i], (card, spec.embed_dim)) * scale
            for i, card in enumerate(spec.cat_cardinalities)]
        deep_in = spec.numeric_dim + n_cat * spec.embed_dim
        deep_spec = NNModelSpec(input_dim=deep_in,
                                hidden_nodes=spec.hidden_nodes,
                                activations=spec.activations, output_dim=1,
                                output_activation="linear")
        params["deep"] = nn_init(keys[-2], deep_spec, "he")
    if spec.wide_enable:
        params["wide_cat"] = [jnp.zeros((card,), jnp.float32)
                              for card in spec.cat_cardinalities]
        params["wide_num"] = jnp.zeros((spec.numeric_dim, 1), jnp.float32)
    params["bias"] = jnp.zeros((1,), jnp.float32)
    return params


# one-hot-matmul lowering cap on TOTAL one-hot elements (N * C * max_card
# — a single high-cardinality column inflates the tensor even at small
# batch): worth materializing for training minibatches (embedding grads
# become matmuls instead of TPU-serialized scatters, measured ~26x on the
# bench step), but a full-dataset scoring pass or a 50k-card column would
# blow HBM — those keep the gather.  33.5M elements = 134 MB f32.
_ONEHOT_MAX_ELEMS = 1 << 25


def _cat_onehot(params: Dict, x_cat):
    """[N, C, K] one-hot over per-column-clipped indices (K = max
    cardinality; a column's padding lanes never activate because its
    indices clip below its own cardinality)."""
    tabs = params.get("embed") or params.get("wide_cat")
    cards = jnp.asarray([t.shape[0] for t in tabs])
    idx = jnp.clip(x_cat, 0, cards[None, :] - 1)
    return jax.nn.one_hot(idx, int(max(t.shape[0] for t in tabs)),
                          dtype=jnp.float32)


def forward_logits(params: Dict, spec: WDLModelSpec, x_num, x_cat):
    """x_num [N, numeric_dim] float; x_cat [N, n_cat] int bin indices.

    Embedding/wide lookups lower two ways: small (training) batches build
    the categorical one-hot ONCE and feed MXU einsums — the backward pass
    is then matmuls, not one scatter-add per column (the per-column
    ``table[idx]`` loop's gathers backprop as scatters the TPU
    serializes); large (scoring) batches keep the per-column gather."""
    n = x_num.shape[0] if spec.numeric_dim else x_cat.shape[0]
    tabs = params.get("embed") or params.get("wide_cat")
    # compute dtype follows the weights (the bf16/mixed training ladder
    # casts the whole param tree): activations run narrow, the logit
    # accumulates in f32 so the sigmoid/loss keep f32 range.  f32 params
    # leave the graph unchanged.
    cdt = tabs[0].dtype if tabs else (
        params["deep"][0]["w"].dtype if spec.deep_enable else jnp.float32)
    use_onehot = bool(tabs) and (
        x_cat.shape[0] * x_cat.shape[1]
        * max(t.shape[0] for t in tabs) <= _ONEHOT_MAX_ELEMS)
    if tabs and not use_onehot:
        # gather lowering: do the lookups here, then share the dense half
        # with the sharded paths so classic-vs-sharded scores stay bitwise
        emb = wide_rows = None
        if spec.deep_enable:
            emb = jnp.stack([
                t[jnp.clip(x_cat[:, i], 0, t.shape[0] - 1)]
                for i, t in enumerate(params["embed"])], axis=1)
        if spec.wide_enable:
            wide_rows = jnp.stack([
                v[jnp.clip(x_cat[:, i], 0, v.shape[0] - 1)]
                for i, v in enumerate(params["wide_cat"])], axis=1)
        return forward_logits_gathered(params, spec, x_num, emb, wide_rows)
    if cdt != jnp.float32 and spec.numeric_dim:
        x_num = x_num.astype(cdt)
    oh = _cat_onehot(params, x_cat) if use_onehot else None
    if oh is not None and cdt != jnp.float32:
        # 0/1 one-hot is exact in bf16; keeping it narrow keeps the
        # lookup einsums' operands (and their grads) narrow too
        oh = oh.astype(cdt)
    logit = jnp.zeros((n, 1)) + params["bias"].astype(jnp.float32)
    if spec.deep_enable:
        parts = [x_num] if spec.numeric_dim else []
        if use_onehot:
            k = oh.shape[-1]
            stacked = jnp.stack([
                jnp.pad(t, ((0, k - t.shape[0]), (0, 0)))
                if t.shape[0] != k else t
                for t in params["embed"]])                # [C, K, E]
            # HIGHEST precision: this einsum is a LOOKUP — default/bf16
            # matmul precision would silently round every table value to
            # bf16 per step (the gather it replaces was exact; same trap
            # as the histogram kernel's convert-round-trip fold)
            emb = jnp.einsum("nck,cke->nce", oh, stacked,
                             precision=jax.lax.Precision.HIGHEST)
            parts.append(emb.reshape(n, -1))             # == concat order
        else:
            for i, table in enumerate(params["embed"]):
                idx = jnp.clip(x_cat[:, i], 0, table.shape[0] - 1)
                parts.append(table[idx])
        h = jnp.concatenate(parts, axis=1)
        from .nn import ACTIVATIONS
        acts = [ACTIVATIONS[a.lower()] for a in spec.activations]
        for li, layer in enumerate(params["deep"][:-1]):
            h = acts[li % len(acts)](h @ layer["w"] + layer["b"])
        last = params["deep"][-1]
        logit = logit + h @ last["w"] + last["b"]
    if spec.wide_enable:
        wide = jnp.zeros((n, 1))
        if use_onehot:
            k = oh.shape[-1]
            wstack = jnp.stack([
                jnp.pad(v, (0, k - v.shape[0]))
                if v.shape[0] != k else v
                for v in params["wide_cat"]])             # [C, K]
            wide = wide + jnp.einsum(
                "nck,ck->n", oh, wstack,
                precision=jax.lax.Precision.HIGHEST)[:, None]
        else:
            for i, wvec in enumerate(params["wide_cat"]):
                idx = jnp.clip(x_cat[:, i], 0, wvec.shape[0] - 1)
                wide = wide + wvec[idx][:, None]
        if spec.numeric_dim:
            wide = wide + x_num @ params["wide_num"]
        logit = logit + wide
    return logit


@jax.custom_vjp
def _lookup_barrier(ops):
    """Differentiable ``optimization_barrier`` (no autodiff rule upstream):
    identity that XLA may not fuse across, both directions — the backward
    barrier keeps the dense half's cotangents identical across paths before
    they enter the per-path lookup transposes (scatter-add vs all_gather)."""
    return jax.lax.optimization_barrier(ops)


def _lookup_barrier_fwd(ops):
    return jax.lax.optimization_barrier(ops), None


def _lookup_barrier_bwd(_, cts):
    return (jax.lax.optimization_barrier(cts),)


_lookup_barrier.defvjp(_lookup_barrier_fwd, _lookup_barrier_bwd)


def forward_logits_gathered(params: Dict, spec: WDLModelSpec, x_num,
                            emb, wide_rows):
    """The dense half of the gather lowering with the categorical lookups
    already done: ``emb`` [N, C, E] embedding rows, ``wide_rows`` [N, C]
    wide weights (either may be None when that side is off).  The sharded
    trainer and the sharded serving path both feed their psum-scattered /
    psum'd lookups through THIS function, so their arithmetic is the
    replicated gather path's bit for bit.

    The barrier pins that contract: without it XLA fuses the lookup
    (gather here, psum/psum_scatter in the sharded paths) into the dense
    half and reassociates the final logit adds differently per caller —
    a last-ulp drift that breaks bit-parity between the paths."""
    if emb is not None or wide_rows is not None:
        emb, wide_rows = _lookup_barrier((emb, wide_rows))
    if spec.deep_enable and emb is not None:
        n = emb.shape[0]
        cdt = emb.dtype
    elif wide_rows is not None:
        n = wide_rows.shape[0]
        cdt = wide_rows.dtype
    else:
        n = x_num.shape[0]
        cdt = params["deep"][0]["w"].dtype if spec.deep_enable \
            else jnp.float32
    if cdt != jnp.float32 and spec.numeric_dim:
        x_num = x_num.astype(cdt)
    logit = jnp.zeros((n, 1)) + params["bias"].astype(jnp.float32)
    if spec.deep_enable:
        parts = [x_num] if spec.numeric_dim else []
        for i in range(emb.shape[1]):
            parts.append(emb[:, i, :])
        h = jnp.concatenate(parts, axis=1)
        from .nn import ACTIVATIONS
        acts = [ACTIVATIONS[a.lower()] for a in spec.activations]
        for li, layer in enumerate(params["deep"][:-1]):
            h = acts[li % len(acts)](h @ layer["w"] + layer["b"])
        last = params["deep"][-1]
        logit = logit + h @ last["w"] + last["b"]
    if spec.wide_enable:
        wide = jnp.zeros((n, 1))
        for i in range(wide_rows.shape[1]):
            wide = wide + wide_rows[:, i][:, None]
        if spec.numeric_dim:
            wide = wide + x_num @ params["wide_num"]
        logit = logit + wide
    return logit


def forward(params: Dict, spec: WDLModelSpec, x_num, x_cat):
    return jax.nn.sigmoid(forward_logits(params, spec, x_num, x_cat))


# ---------------------------------------------------------- hashed IDs
def hash_plan(spec: WDLModelSpec):
    """(buckets, [(col_pos, key64), ...]) from the spec's hashed-ID plan,
    or None when the spec has no hashed columns.  The plan is recorded in
    ``spec.extra`` at train time so serving replays the identical map."""
    buckets = int(spec.extra.get("hash_buckets", 0) or 0)
    cols = spec.extra.get("hashed_cols") or []
    keys = spec.extra.get("hash_keys") or []
    if buckets <= 0 or not cols:
        return None
    return buckets, [(int(c), int(k)) for c, k in zip(cols, keys)]


def apply_hash_host(spec: WDLModelSpec, x_cat: np.ndarray) -> np.ndarray:
    """Map hashed-ID columns of a host [N, C] bin matrix into bucket
    space (identity when the spec has no hash plan).  NOT idempotent —
    exactly one layer owns the call per path (trainers and
    ``IndependentWDLModel.compute``; ``forward`` consumes bucket ids)."""
    plan = hash_plan(spec)
    if plan is None:
        return x_cat
    from ..ops import hashing
    buckets, cols = plan
    out = np.array(x_cat, np.int32, copy=True)
    for c, key in cols:
        out[:, c] = hashing.hash_bucket_host(x_cat[:, c], key, buckets)
    return out


def apply_hash_device(spec: WDLModelSpec, x_cat):
    """In-graph replay of :func:`apply_hash_host` for the serving path —
    bit-identical bucket ids (splitmix64 over uint32 limbs)."""
    plan = hash_plan(spec)
    if plan is None:
        return x_cat
    from ..ops import hashing
    buckets, cols = plan
    parts = [x_cat[:, i] for i in range(x_cat.shape[1])]
    for c, key in cols:
        parts[c] = hashing.hash_bucket_device(parts[c], key, buckets)
    return jnp.stack(parts, axis=1)


def per_row_bce(p, y):
    """Clipped binary cross-entropy per row: p, y are [N, 1] -> [N].
    The ONE definition of the WDL loss — trainers (in-RAM, streamed, eval
    sums) all call this so the objective cannot drift between paths."""
    return -(y * jnp.log(jnp.clip(p, 1e-7, 1.0))
             + (1 - y) * jnp.log(jnp.clip(1 - p, 1e-7, 1.0))).sum(axis=-1)


def weighted_loss(params, spec: WDLModelSpec, x_num, x_cat, y, w,
                  l2: float = 0.0):
    p = forward(params, spec, x_num, x_cat)
    per = per_row_bce(p, y)
    loss = (per * w).sum() / jnp.maximum(w.sum(), 1e-9)
    if l2:
        reg = sum((layer["w"] ** 2).sum() for layer in params.get("deep", []))
        reg = reg + sum((t ** 2).sum() for t in params.get("embed", []))
        loss = loss + l2 * reg
    return loss


def l2_grads(params: Dict, l2: float) -> Dict:
    """Gradient of weighted_loss's L2 term — deep weights and embedding
    tables ONLY (bias/wide stay unpenalized), so the streamed trainer's
    accumulated-gradient update regularizes exactly what the in-RAM loss
    does."""
    import jax
    g = jax.tree_util.tree_map(jnp.zeros_like, params)
    for i, layer in enumerate(params.get("deep", [])):
        g["deep"][i]["w"] = 2.0 * l2 * layer["w"]
    for i, t in enumerate(params.get("embed", [])):
        g["embed"][i] = 2.0 * l2 * t
    return g


# ------------------------------------------------------------- save/load
def save_model(path: str, spec: WDLModelSpec, params: Dict) -> None:
    arrays = {"__spec__": np.frombuffer(spec.to_json().encode(), np.uint8),
              "bias": np.asarray(params["bias"], np.float32)}
    if spec.deep_enable:
        for i, t in enumerate(params["embed"]):
            arrays[f"emb{i}"] = np.asarray(t, np.float32)
        for i, layer in enumerate(params["deep"]):
            arrays[f"dw{i}"] = np.asarray(layer["w"], np.float32)
            arrays[f"db{i}"] = np.asarray(layer["b"], np.float32)
    if spec.wide_enable:
        for i, t in enumerate(params["wide_cat"]):
            arrays[f"wc{i}"] = np.asarray(t, np.float32)
        arrays["wn"] = np.asarray(params["wide_num"], np.float32)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    ioutil.atomic_write_bytes(path, buf.getvalue())


def load_model(path: str) -> Tuple[WDLModelSpec, Dict]:
    data = np.load(path)
    spec = WDLModelSpec.from_json(bytes(data["__spec__"]).decode())
    params: Dict[str, Any] = {"bias": jnp.asarray(data["bias"])}
    n_cat = len(spec.cat_cardinalities)
    if spec.deep_enable:
        params["embed"] = [jnp.asarray(data[f"emb{i}"]) for i in range(n_cat)]
        params["deep"] = []
        i = 0
        while f"dw{i}" in data:
            params["deep"].append({"w": jnp.asarray(data[f"dw{i}"]),
                                   "b": jnp.asarray(data[f"db{i}"])})
            i += 1
    if spec.wide_enable:
        params["wide_cat"] = [jnp.asarray(data[f"wc{i}"]) for i in range(n_cat)]
        params["wide_num"] = jnp.asarray(data["wn"])
    return spec, params


class IndependentWDLModel:
    """Standalone scorer (reference ``IndependentWDLModel.java``); consumes
    both planes: normalized numerics + categorical bin indices."""

    input_kind = "both"

    def __init__(self, spec: WDLModelSpec, params: Dict):
        self.spec = spec
        self.params = params
        self._fwd = jax.jit(lambda p, xn, xc: forward(p, spec, xn, xc))

    @classmethod
    def load(cls, path: str) -> "IndependentWDLModel":
        return cls(*load_model(path))

    def compute(self, x_num: np.ndarray, x_cat: np.ndarray) -> np.ndarray:
        x_cat = apply_hash_host(self.spec, np.asarray(x_cat, np.int32))
        return np.asarray(self._fwd(self.params,
                                    jnp.asarray(x_num, jnp.float32),
                                    jnp.asarray(x_cat, jnp.int32)))

    def compute_full(self, x: np.ndarray, bins: np.ndarray) -> np.ndarray:
        """Score from the full transform planes: slice out this model's
        numeric feature block and categorical bin columns (indices recorded
        at train time in the spec)."""
        nf = self.spec.extra.get("num_feat_idx", [])
        cf = self.spec.extra.get("cat_col_idx", [])
        x_num = x[:, nf] if nf else np.zeros((len(x), 0), np.float32)
        x_cat = bins[:, cf] if cf else np.zeros((len(x), 0), np.int32)
        return self.compute(x_num, x_cat)
