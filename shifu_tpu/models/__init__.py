"""Model specs — standalone scorers + serialization.

Each saved model file is self-contained (spec json + arrays in one npz blob),
the role of the reference's ``Independent*Model`` + ``Binary*Serializer``
family (``dtrain/nn/IndependentNNModel.java``,
``dt/IndependentTreeModel.java``, ``wdl/IndependentWDLModel.java``).
``load_any`` sniffs the embedded spec kind, so ``Scorer`` needn\'t know
algorithms.
"""

from __future__ import annotations

import json

import numpy as np


def spec_kind(path: str) -> str:
    data = np.load(path)
    return json.loads(bytes(data["__spec__"]).decode()).get("kind", "nn")


def load_any(path: str):
    """Load any saved model file -> object with ``.compute(x) -> [n, out]``."""
    kind = spec_kind(path)
    if kind == "nn":
        from .nn import IndependentNNModel
        return IndependentNNModel.load(path)
    # LR models are saved as degenerate 0-hidden-layer NN specs (kind
    # "nn", extra.algorithm == "LR") — one scorer path, no parallel LR code.
    if kind == "tree":
        from .tree import IndependentTreeModel
        return IndependentTreeModel.load(path)
    if kind == "wdl":
        from .wdl import IndependentWDLModel
        return IndependentWDLModel.load(path)
    if kind == "tower":
        from .towers import IndependentTowerModel
        return IndependentTowerModel.load(path)
    if kind == "svm":
        from .svm import IndependentSVMModel
        return IndependentSVMModel.load(path)
    raise ValueError(f"unknown model kind {kind!r} in {path}")
