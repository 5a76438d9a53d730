"""What every tower of the ``TENSORFLOW`` slot shares, and the towers by name.

``train#params.Tower`` names a module of this package (:data:`TOWERS`).  The
trainer, ``eval`` and the entry points' refusals find it through
:func:`module`; a tower's module gives

- ``spec_from_params(tower_params, column_nums, column_bins, feature_names)``
  (``TowerParams`` = the architecture's config.json keys and the share; every
  problem named in one coded error) and its ``TowerSpec`` (a dataclass with
  :class:`RowTokens`, ``tower`` = its name),
- ``init_params(key, spec)``,
- ``train_loss(params, spec, ids, row_w, key, specials) -> (loss, aux)``:
  the microbatch's loss; ``aux`` holds ``loss_sum`` and ``positions`` (an
  epoch's error is their sums' quotient) and the counters of
  ``counter_shapes(spec)``,
- ``tag_logits(params, spec, feature_ids, tag0_id, mask_id) -> [n, 2]``,
- ``SCOPES`` (its ``jax.named_scope`` names, most specific first) and
  ``OBS_COUNTERS`` (counter of ``aux`` -> the telemetry counter it feeds),
- optionally ``sequence_block(spec)``: the tower takes several rows a sequence
  (``train#params.RowsPerSequence``); its ``train_loss`` then gets what
  :func:`pack_rows` makes of the microbatch — ids ``[sequences, positions]``
  padded to whole blocks of that many positions and a weight a position —
  and ``after_step(params, aux, spec) -> (params, aux)``: what the tower does
  to its parameters after each optimizer step, outside the gradient.

Shared here: the tokeniser (a row of the binned plane is a sequence: one
token a column, id = the column's offset + its bin, then the specials), the
packing, the causal depthwise convolution (``nemotron_h``, ``lfm2_moe``), the
``.tower`` file and ``eval``'s scorer.
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import asdict
from typing import Any, Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..config.errors import ErrorCode, ShifuError

TOWERS = {"sdar_moe": "tower_sdar", "nemotron_h": "tower_nemotron_h", "afmoe": "tower_afmoe",
          "lfm2_moe": "tower_lfm2", "deepseek_v3": "tower_deepseek_v3"}
SPECIALS = ("TAG0", "TAG1", "MASK", "PAD")


def module(name: str):
    """The tower's module; an unknown name is a coded error."""
    if name not in TOWERS:
        raise ShifuError(ErrorCode.ERROR_MODELCONFIG_NOT_VALIDATION,
                         f"train#params.Tower {name!r} is not one of {sorted(TOWERS)}")
    return importlib.import_module("." + TOWERS[name], __package__)


class RowTokens:
    """The token layout of a spec with ``column_bins`` and ``block_length``:
    the feature tokens padded to whole blocks, then one block that starts
    with the tag."""

    @property
    def n_features(self) -> int:
        return len(self.column_bins)

    @property
    def feature_len(self) -> int:
        b = self.block_length
        return -(-self.n_features // b) * b

    @property
    def seq_len(self) -> int:
        return self.feature_len + self.block_length

    @property
    def n_ids(self) -> int:
        return int(sum(b + 1 for b in self.column_bins)) + len(SPECIALS)

    def special(self, name: str) -> int:
        return self.n_ids - len(SPECIALS) + SPECIALS.index(name)

    def offsets(self) -> np.ndarray:
        sizes = np.asarray(self.column_bins, np.int64) + 1
        return np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int32)

    def to_json(self) -> str:
        return json.dumps(asdict(self))

    @classmethod
    def from_json(cls, text: str):
        return cls(**json.loads(text))

    def token_problems(self) -> list:
        """More ids than the slice holds is an error, never a clamp."""
        out = []
        if self.n_ids > self.vocab_size:
            out.append(f"the plane's columns need {self.n_ids} token ids "
                       f"({self.n_features} columns' bins + {len(SPECIALS)}), the "
                       f"vocabulary slice holds {self.vocab_size}")
        if self.seq_len > self.max_position_embeddings:
            out.append(f"a row is {self.seq_len} positions, max_position_embeddings "
                       f"{self.max_position_embeddings}")
        return out


def tokenize(spec, bins: np.ndarray, y: np.ndarray) -> np.ndarray:
    """[n, C] bins + [n] targets -> [n, S] int32 ids."""
    bins = np.asarray(bins)
    n, c = bins.shape
    if c != spec.n_features:
        raise ValueError(f"the plane has {c} columns, the tower {spec.n_features}")
    over = bins.max(axis=0, initial=0) > np.asarray(spec.column_bins)
    if over.any():
        j = int(np.flatnonzero(over)[0])
        raise ShifuError(ErrorCode.ERROR_MODELCONFIG_NOT_VALIDATION,
                         f"column {spec.column_nums[j] if spec.column_nums else j} holds bin "
                         f"{int(bins[:, j].max())}, ColumnConfig gives it "
                         f"{spec.column_bins[j]} value bins and the missing bin")
    ids = np.full((n, spec.seq_len), spec.special("PAD"), np.int32)
    ids[:, :c] = bins.astype(np.int32) + spec.offsets()[None, :]
    ids[:, spec.feature_len] = np.where(np.asarray(y) > 0.5, spec.special("TAG1"),
                                        spec.special("TAG0"))
    return ids


def pad_to_block(ids, block: int, pad_id):
    """[n, L] -> [n, L rounded up to whole blocks], filled with ``pad_id``."""
    return jnp.pad(ids, ((0, 0), (0, -ids.shape[1] % block)), constant_values=pad_id)


def causal_conv(x, w):
    """The causal depthwise convolution along a sequence: x [n, T, C], w [K, C]
    -> [n, T, C], ``out_t = sum_j w_j x_{t-K+1+j}`` with x before the first
    position 0 — the last tap is the current position (a ``Conv1d(groups=C,
    padding=K-1)`` cut to T).  A bias and an activation are the caller's."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * w[j] for j in range(k))


def pack_rows(ids, row_w, rows_per_sequence: int, block: int, pad_id):
    """Each ``rows_per_sequence`` consecutive rows of a microbatch laid end to
    end (a row's feature tokens then its tag), padded with ``PAD`` to whole
    blocks.  ids [n, S], row_w [n] -> (ids [n / R, L], weights [n / R, L]:
    each position's row's weight, 0 on the padding)."""
    n, s = ids.shape
    seqs = n // rows_per_sequence
    w = jnp.repeat(row_w[:, None], s, axis=1).reshape(seqs, rows_per_sequence * s)
    return (pad_to_block(ids.reshape(seqs, rows_per_sequence * s), block, pad_id),
            pad_to_block(w, block, 0.0))


def pack_plan(spec, microbatch: int, rows_per_sequence: int, block: int) -> Dict[str, int]:
    """What :func:`pack_rows` makes of a microbatch, in numbers; a packing the
    tower cannot take is a coded error."""
    problems = []
    if rows_per_sequence < 1 or microbatch % rows_per_sequence:
        problems.append(f"train#params.MiniBatchs {microbatch} is not whole sequences of "
                        f"RowsPerSequence {rows_per_sequence} rows")
    if rows_per_sequence * spec.seq_len > spec.max_position_embeddings:
        problems.append(f"RowsPerSequence {rows_per_sequence} x {spec.seq_len} positions a row "
                        f"exceeds max_position_embeddings {spec.max_position_embeddings}")
    if problems:
        raise ShifuError(ErrorCode.ERROR_MODELCONFIG_NOT_VALIDATION, "; ".join(problems))
    used = rows_per_sequence * spec.seq_len
    positions = -(-used // block) * block
    return {"rows": microbatch, "sequences": microbatch // rows_per_sequence,
            "positions": positions, "pad_positions": positions - used}


def n_params(params) -> int:
    return int(sum(np.prod(a.shape) for a in jax.tree_util.tree_leaves(params)))


# ------------------------------------------------------------- the model file
def flat_names(tree: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    """Nested dicts of arrays -> ``a.b.c`` -> array."""
    out: Dict[str, Any] = {}
    for k, v in tree.items():
        out.update(flat_names(v, prefix + k + ".") if isinstance(v, dict) else {prefix + k: v})
    return out


def nest_names(flat: Dict[str, Any]) -> Dict[str, Any]:
    """``a.b.c`` -> array back into nested dicts: :func:`flat_names`' inverse."""
    out: Dict[str, Any] = {}
    for name, value in flat.items():
        node = out
        *parents, leaf = name.split(".")
        for part in parents:
            node = node.setdefault(part, {})
        node[leaf] = value
    return out


def save_model(path: str, spec, params) -> int:
    """Self-contained ``.tower`` file: an uncompressed npz of the f32 arrays +
    the spec json, written beside the path and renamed into place (a
    gigabyte-sized file is never buffered whole).  Returns its bytes.  The
    write (``np.savez`` into the temp file, its close included) and the
    commit (the rename) are the spans ``tower.save.write`` (bytes) and
    ``tower.save.commit``; a caller that times the fetch hands in host arrays."""
    arrays = {k: np.asarray(v, np.float32) for k, v in flat_names(params).items()}
    arrays["__spec__"] = np.frombuffer(spec.to_json().encode(), dtype=np.uint8)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp{os.getpid()}"             # ioutil's temp name: sweep_orphan_tmp finds it
    try:
        with obs.span("tower.save.write") as sp:
            with open(tmp, "wb") as f:
                np.savez(f, **arrays)
            size = os.path.getsize(tmp)
            sp.set(bytes=size)
        with obs.span("tower.save.commit"):
            os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.remove(tmp)
        raise
    return size


def load_model(path: str) -> Tuple[Any, Dict[str, Any]]:
    """(spec, params) of any tower's file: the spec names its tower."""
    data = np.load(path)
    text = bytes(data["__spec__"]).decode()
    spec = module(json.loads(text)["tower"]).TowerSpec.from_json(text)
    return spec, nest_names({name: data[name] for name in data.files if name != "__spec__"})


class IndependentTowerModel:
    """Scores binned rows with a saved tower (``input_kind = 'bins'``)."""

    input_kind = "bins"
    SCORE_ROWS = 16                  # rows a scoring program takes

    def __init__(self, spec, params):
        self.spec = spec
        self.params = jax.device_put(params)
        tag_logits = module(spec.tower).tag_logits
        self._fwd = jax.jit(lambda p, ids, tag0, mask: tag_logits(p, spec, ids, tag0, mask))

    @classmethod
    def load(cls, path: str) -> "IndependentTowerModel":
        return cls(*load_model(path))

    @property
    def max_bin_id(self) -> int:
        """The largest bin id a column can carry (its missing bin): what
        ``ops/tree_quant.ensemble_bins_dtype`` sizes the bins input by."""
        return max(self.spec.column_bins, default=0)

    def compute(self, bins) -> np.ndarray:
        """[n, C] bins -> [n, 1] p(tag = 1) = sigmoid(logit_TAG1 - logit_TAG0)."""
        spec = self.spec
        ids = tokenize(spec, np.asarray(bins), np.zeros(len(bins)))[:, :spec.feature_len]
        out = np.empty((len(ids), 1), np.float32)
        tag0, mask = jnp.int32(spec.special("TAG0")), jnp.int32(spec.special("MASK"))
        for a in range(0, len(ids), self.SCORE_ROWS):
            part = ids[a: a + self.SCORE_ROWS]
            pad = np.concatenate([part, np.repeat(part[-1:], self.SCORE_ROWS - len(part), 0)])
            two = np.asarray(self._fwd(self.params, jnp.asarray(pad), tag0, mask))[:len(part)]
            out[a: a + len(part), 0] = 1.0 / (1.0 + np.exp(-(two[:, 1] - two[:, 0]).astype(np.float64)))
        return out


# ------------------------------------------------------------------ refusals
def refuse_dir(model_set_dir: str, what: str) -> None:
    """:func:`refuse` for an entry point that has only the directory."""
    path = os.path.join(model_set_dir, "ModelConfig.json")
    if os.path.isfile(path):
        from ..config import ModelConfig
        refuse(ModelConfig.load(path), what)


def refuse(model_config, what: str) -> None:
    """``export``, ``serve``, ``combo`` and ``varselect -wrapper`` have no
    tower path yet: one coded error each, before anything is loaded."""
    from ..config.model_config import Algorithm
    tr = model_config.train
    if tr.algorithm == Algorithm.TENSORFLOW and (tr.params or {}).get("Tower"):
        raise ShifuError(ErrorCode.ERROR_UNSUPPORT_ALG,
                         f"`{what}` cannot take a tower (train#params.Tower = "
                         f"{tr.params['Tower']!r}): towers train and are scored by "
                         "`eval`; use an NN, tree or WDL model set here")
