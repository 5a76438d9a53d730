"""NN model: jitted MLP forward/backprop — the Encog flat-network replacement.

Covers the reference's NN stack (``core/dtrain/nn/``): custom activations
(``nn/Activation*.java`` — leakyrelu/ptanh/relu/swish plus Encog
sigmoid/tanh/linear), losses (``nn/*ErrorCalculation.java`` — log / squared /
absolute), weight init randomizers (Xavier/He/Lecun,
``core/dtrain/random/``), dropout (``BasicDropoutLayer``), and the standalone
scorer role of ``IndependentNNModel.java`` (a saved spec scores with no
trainer dependencies).

Params are a list-of-layers pytree ``[{"w": [in,out], "b": [out]}, ...]`` —
matmul-shaped for the MXU; batched rows hit one fused kernel per layer.
"""

from __future__ import annotations

import io
import json
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import ioutil

import jax
import jax.numpy as jnp

SPEC_VERSION = 1

# ----------------------------------------------------------- activations
ACTIVATIONS: Dict[str, Callable] = {
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "relu": jax.nn.relu,
    "leakyrelu": lambda x: jnp.where(x >= 0, x, 0.01 * x),
    "ptanh": lambda x: jnp.where(x >= 0, jnp.tanh(x), 0.25 * jnp.tanh(x)),
    "swish": lambda x: x * jax.nn.sigmoid(x),
    "linear": lambda x: x,
    "log": lambda x: jnp.where(x >= 0, jnp.log1p(x), -jnp.log1p(-x)),
    "sin": jnp.sin,
    "softmax": lambda x: jax.nn.softmax(x, axis=-1),
}


def activation(name: str) -> Callable:
    key = (name or "sigmoid").lower()
    if key not in ACTIVATIONS:
        raise ValueError(f"unknown activation {name!r}; one of {sorted(ACTIVATIONS)}")
    return ACTIVATIONS[key]


@dataclass
class NNModelSpec:
    """Network shape + metadata; serialized alongside weights so the saved
    model scores standalone (reference ``IndependentNNModel.java``)."""
    input_dim: int
    hidden_nodes: List[int]
    activations: List[str]
    output_dim: int = 1
    output_activation: str = "sigmoid"
    loss: str = "squared"           # reference default squared error
    column_nums: Optional[List[int]] = None
    feature_names: Optional[List[str]] = None
    extra: Dict[str, Any] = field(default_factory=dict)

    def layer_dims(self) -> List[Tuple[int, int]]:
        dims = [self.input_dim] + list(self.hidden_nodes) + [self.output_dim]
        return list(zip(dims[:-1], dims[1:]))

    def to_json(self) -> str:
        return json.dumps({
            "version": SPEC_VERSION, "kind": "nn",
            "input_dim": self.input_dim, "hidden_nodes": self.hidden_nodes,
            "activations": self.activations, "output_dim": self.output_dim,
            "output_activation": self.output_activation, "loss": self.loss,
            "column_nums": self.column_nums, "feature_names": self.feature_names,
            "extra": self.extra})

    @classmethod
    def from_json(cls, s: str) -> "NNModelSpec":
        d = json.loads(s)
        return cls(input_dim=d["input_dim"], hidden_nodes=d["hidden_nodes"],
                   activations=d["activations"], output_dim=d.get("output_dim", 1),
                   output_activation=d.get("output_activation", "sigmoid"),
                   loss=d.get("loss", "squared"),
                   column_nums=d.get("column_nums"),
                   feature_names=d.get("feature_names"),
                   extra=d.get("extra", {}))


# ------------------------------------------------------------------- init
def init_params(key, spec: NNModelSpec, initializer: str = "xavier") -> List[Dict]:
    """Weight init per reference randomizers (``core/dtrain/random/``:
    Xavier/He/Lecun; default Xavier)."""
    init = (initializer or "xavier").lower()
    params = []
    for fan_in, fan_out in spec.layer_dims():
        key, sub = jax.random.split(key)
        if init in ("he", "herandomizer"):
            scale = np.sqrt(2.0 / fan_in)
            w = jax.random.normal(sub, (fan_in, fan_out)) * scale
        elif init in ("lecun", "lecunrandomizer"):
            scale = np.sqrt(1.0 / fan_in)
            w = jax.random.normal(sub, (fan_in, fan_out)) * scale
        else:  # xavier uniform
            limit = np.sqrt(6.0 / (fan_in + fan_out))
            w = jax.random.uniform(sub, (fan_in, fan_out), minval=-limit, maxval=limit)
        params.append({"w": w.astype(jnp.float32),
                       "b": jnp.zeros((fan_out,), jnp.float32)})
    return params


# ---------------------------------------------------------------- forward
def forward(params: List[Dict], spec: NNModelSpec, x, *,
            dropout_rate: float = 0.0, rng=None):
    """MLP forward.  Hidden dropout (inverted scaling) only when a key is
    given — eval path stays deterministic.

    The compute dtype follows the WEIGHTS: bf16 params (the mixed/bf16
    training ladder) pull the input and every hidden activation down to
    bf16 — matmuls feed the MXU at native rate and activations halve
    their HBM footprint — while the head logits widen back to f32 so the
    output activation and loss keep f32 dynamic range.  f32 params leave
    the graph byte-identical to before."""
    acts = [activation(a) for a in spec.activations]
    cdt = params[0]["w"].dtype if params else jnp.float32
    h = x.astype(cdt) if cdt != jnp.float32 else x
    n_hidden = len(params) - 1
    for i, layer in enumerate(params[:-1]):
        h = acts[i % max(1, len(acts))](h @ layer["w"] + layer["b"])
        # rng gates dropout statically; the RATE may be a tracer (stacked
        # grid trials carry a per-member dropout array)
        if rng is not None and _nonzero(dropout_rate):
            rng, sub = jax.random.split(rng)
            keep_p = 1.0 - dropout_rate
            keep = jax.random.bernoulli(sub, keep_p, h.shape)
            # divide in h's dtype: a strong-typed f32 keep_p (per-member
            # hyper tracer) would silently widen a bf16 ladder back to f32
            h = jnp.where(keep, h / jnp.asarray(keep_p, h.dtype), 0.0)
    out = h @ params[-1]["w"] + params[-1]["b"]
    if out.dtype != jnp.float32:
        out = out.astype(jnp.float32)
    return activation(spec.output_activation)(out)


def _nonzero(v) -> bool:
    """Static gate for optional terms: a concrete 0.0 skips the op entirely;
    a tracer (per-member hyper array under vmap) always includes it."""
    return not (isinstance(v, (int, float)) and float(v) == 0.0)


LOSSES = {
    "squared": lambda p, y: (p - y) ** 2,
    "absolute": lambda p, y: jnp.abs(p - y),
    "log": lambda p, y: -(y * jnp.log(jnp.clip(p, 1e-7, 1.0))
                          + (1 - y) * jnp.log(jnp.clip(1 - p, 1e-7, 1.0))),
    # hinge on a linear head: y in {0,1} maps to targets {-1,+1}; the SVM
    # path (reference ``core/alg/SVMTrainer.java``) is this loss on the
    # 0-hidden-layer net
    "hinge": lambda p, y: jnp.maximum(0.0, 1.0 - (2.0 * y - 1.0) * p),
}


def per_row_loss(pred, y, spec: NNModelSpec):
    """Per-row loss for any head.  Multi-class (output_dim > 1): y holds the
    class index, loss is softmax cross-entropy — the NATIVE multi-class mode
    (reference ``ModelTrainConf.MultipleClassification.NATIVE``).  Binary /
    regression: the configured elementwise loss."""
    if spec.output_dim > 1:
        oh = jax.nn.one_hot(jnp.asarray(y).reshape(-1).astype(jnp.int32),
                            spec.output_dim, dtype=pred.dtype)
        return -(oh * jnp.log(jnp.clip(pred, 1e-7, 1.0))).sum(axis=-1)
    lfn = LOSSES.get(spec.loss, LOSSES["squared"])
    return lfn(pred, y).sum(axis=-1)


def weighted_loss(params, spec: NNModelSpec, x, y, w, *,
                  l2: float = 0.0, l1: float = 0.0,
                  dropout_rate: float = 0.0, rng=None):
    """Per-batch mean weighted loss + L1/L2 (reference ``Weight.java:201-213``
    applies reg in the update; applying it in the loss is equivalent under
    gradient descent and lets XLA fuse it)."""
    pred = forward(params, spec, x, dropout_rate=dropout_rate, rng=rng)
    per_row = per_row_loss(pred, y, spec)
    denom = jnp.maximum(w.sum(), 1e-9)
    loss = (per_row * w).sum() / denom
    if _nonzero(l2):
        loss = loss + l2 * sum((layer["w"] ** 2).sum() for layer in params)
    if _nonzero(l1):
        loss = loss + l1 * sum(jnp.abs(layer["w"]).sum() for layer in params)
    return loss


# ------------------------------------------------------------- save/load
def save_model(path: str, spec: NNModelSpec, params) -> None:
    """Self-contained .nn file: npz of weight arrays + the spec json.

    Role of ``BinaryNNSerializer.java`` / ``PersistBasicFloatNetwork``; format
    is ours (npz), not Encog's."""
    arrays = {}
    for i, layer in enumerate(params):
        arrays[f"w{i}"] = np.asarray(layer["w"], np.float32)
        arrays[f"b{i}"] = np.asarray(layer["b"], np.float32)
    arrays["__spec__"] = np.frombuffer(spec.to_json().encode(), dtype=np.uint8)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    ioutil.atomic_write_bytes(path, buf.getvalue())


def load_model(path: str) -> Tuple[NNModelSpec, List[Dict]]:
    data = np.load(path)
    spec = NNModelSpec.from_json(bytes(data["__spec__"]).decode())
    params = []
    for i in range(len(spec.layer_dims())):
        params.append({"w": jnp.asarray(data[f"w{i}"]),
                       "b": jnp.asarray(data[f"b{i}"])})
    return spec, params


class IndependentNNModel:
    """Dependency-light scorer over a saved spec (reference
    ``IndependentNNModel.java``: load once, ``compute()`` per batch)."""

    def __init__(self, spec: NNModelSpec, params):
        self.spec = spec
        self.params = params
        self._fwd = jax.jit(lambda p, x: forward(p, spec, x))

    @classmethod
    def load(cls, path: str) -> "IndependentNNModel":
        return cls(*load_model(path))

    def compute(self, x: np.ndarray) -> np.ndarray:
        return np.asarray(self._fwd(self.params, jnp.asarray(x, jnp.float32)))


def fit_params_into(old_spec: NNModelSpec, old_params, new_spec: NNModelSpec,
                    key, initializer: str = "xavier"):
    """Continuous-training structure fit-in (reference ``NNMaster.java:
    331-362,605-645``): grow a smaller saved net into a larger configured
    one — fresh-init the new shape, then copy each old weight block into
    the top-left corner of the matching layer.  New rows/cols/layers keep
    their fresh init.  Returns None when the old net does not embed (any
    old dim exceeds the new one, or fewer layers configured than saved)."""
    old_dims = old_spec.layer_dims()
    new_dims = new_spec.layer_dims()
    if len(old_dims) > len(new_dims):
        return None
    for (oi, oo), (ni, no) in zip(old_dims, new_dims):
        if oi > ni or oo > no:
            return None
    # the OUTPUT layer must stay last: when layers are added, the old
    # output layer cannot be copied mid-stack meaningfully — only grow
    # same-depth nets or append hidden layers before a fresh output
    params = init_params(key, new_spec, initializer)
    out = []
    for li, layer in enumerate(params):
        if li < len(old_params) and not (
                len(old_dims) < len(new_dims) and li == len(old_params) - 1):
            w = np.asarray(layer["w"]).copy()
            b = np.asarray(layer["b"]).copy()
            ow = np.asarray(old_params[li]["w"])
            ob = np.asarray(old_params[li]["b"])
            w[:ow.shape[0], :ow.shape[1]] = ow
            b[:ob.shape[0]] = ob
            out.append({"w": jnp.asarray(w), "b": jnp.asarray(b)})
        else:
            out.append(layer)
    return out
