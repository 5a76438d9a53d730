"""The synthetic fraud table, from a seed.  NumPy only: the load generator's
child process imports this and must never import jax.

A vectorised sibling of ``examples/make_fraud_data.make_wide`` at a public
table's shape (the configuration's ``table`` block).  One :class:`Table`
serves three consumers:

- :meth:`Table.write_text` — a small pipe-delimited sample the program's own
  ``new -> init -> stats -> norm`` steps run on (genuine Model/ColumnConfig);
- :meth:`Table.binned_chunk` / :meth:`Table.normalised_chunk` — full-size
  planes drawn directly in the binned / normalised domain, shard by shard,
  so no run parses gigabytes of text;
- :func:`json_records` — raw JSON records of the sample for ``POST /score``.

What depends on the *configuration* (column names, cardinalities, which
columns carry signal and how strongly) comes from ``table_seed``; what
depends on ``--seed`` is only the rows.  A seed never changes a shape.
"""

from __future__ import annotations

import json
import os
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np

SEED_MASK = (1 << 63) - 1


def fold_seed(seed: int, *tags: int) -> np.random.Generator:
    """A generator for (seed, tags): any non-negative seed, any width."""
    ss = np.random.SeedSequence([int(seed) & SEED_MASK, *[int(t) for t in tags]])
    return np.random.Generator(np.random.SFC64(ss))


def rank_auc(score: np.ndarray, y: np.ndarray) -> float:
    """AUC of ``score`` against boolean ``y`` (mid-ranks not needed: callers
    pass continuous scores)."""
    n, npos = len(y), int(y.sum())
    if npos == 0 or npos == n:
        return float("nan")
    ranks = np.empty(n)
    ranks[np.argsort(score, kind="mergesort")] = np.arange(1, n + 1)
    return float((ranks[y].sum() - npos * (npos + 1) / 2) / (npos * (n - npos)))


class Table:
    def __init__(self, spec: dict):
        self.n_num = int(spec["numeric_columns"])
        self.n_cat = int(spec["categorical_columns"])
        self.pos_rate = float(spec["positive_rate"])
        self.miss = float(spec["missing_rate"])
        rng = np.random.default_rng(int(spec["table_seed"]))
        lo, hi = spec["cardinality_range"]
        self.card = rng.integers(lo, hi + 1, self.n_cat)
        self.num_names = [f"n{j:03d}" for j in range(self.n_num)]
        self.cat_names = [f"c{j:02d}" for j in range(self.n_cat)]
        self.names = self.num_names + self.cat_names
        k_num = min(int(spec["signal_numeric"]), self.n_num)
        k_cat = min(int(spec["signal_categorical"]), self.n_cat)
        self.sig_num = np.sort(rng.choice(self.n_num, k_num, replace=False))
        self.sig_cat = np.sort(rng.choice(self.n_cat, k_cat, replace=False))
        self.coef = rng.uniform(0.25, 0.7, k_num) * rng.choice([-1.0, 1.0], k_num)
        # per-category effects of the signal categoricals, zero-mean
        self.cat_effect = []
        for j in self.sig_cat:
            e = rng.normal(0.0, 0.5, self.card[j])
            self.cat_effect.append(e - e.mean())

    @property
    def width(self) -> int:
        return self.n_num + self.n_cat

    # ------------------------------------------------------------ labels
    def _logit(self, z_sig: np.ndarray, codes_sig: np.ndarray) -> np.ndarray:
        """z_sig [n, k_num] latent z of the signal numerics (0 = missing),
        codes_sig [n, k_cat] category codes of the signal categoricals."""
        out = z_sig.astype(np.float64) @ self.coef
        for k, eff in enumerate(self.cat_effect):
            out += eff[codes_sig[:, k]]
        return out

    def intercept(self, seed: int) -> float:
        """The intercept that gives ``positive_rate``, by bisection on a
        fixed-size seeded draw of the logit (65,536 rows whatever the
        plane: never a function of the plane's shape)."""
        rng = fold_seed(seed, 11)
        n = 65536
        z = rng.standard_normal((n, len(self.sig_num)))
        z[rng.random(z.shape) < self.miss] = 0.0
        codes = np.stack([rng.integers(0, self.card[j], n) for j in self.sig_cat], 1) \
            if len(self.sig_cat) else np.zeros((n, 0), np.int64)
        base = self._logit(z, codes)
        lo, hi = -30.0, 30.0
        for _ in range(60):
            mid = 0.5 * (lo + hi)
            if (1 / (1 + np.exp(-(base + mid)))).mean() > self.pos_rate:
                hi = mid
            else:
                lo = mid
        return 0.5 * (lo + hi)

    def _labels(self, rng, logit: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        p = 1 / (1 + np.exp(-logit))
        return rng.random(len(p)) < p, p

    # ------------------------------------------------------- text sample
    def _raw(self, z: np.ndarray) -> np.ndarray:
        """Latent z -> raw numeric cells as a user's table has them (skewed
        amounts, count-like, wide scale, plain), three decimals."""
        x = z.astype(np.float64).copy()
        x[:, 0::4] = np.exp(0.6 * x[:, 0::4])
        x[:, 1::4] = np.round(3.0 + 2.0 * x[:, 1::4])
        x[:, 2::4] = 50.0 * x[:, 2::4]
        return np.clip(np.round(x, 3), -999.0, 999.0)

    def write_text(self, out_dir: str, rows: int, seed: int, tag: int = 1) -> dict:
        """``rows`` pipe-delimited records with a header, an id column
        (meta) and the ``tag`` target; returns paths + Bayes AUC."""
        rng = fold_seed(seed, 1, tag)
        z = rng.standard_normal((rows, self.n_num), dtype=np.float32)
        miss = rng.random((rows, self.n_num), dtype=np.float32) < self.miss
        codes = np.stack([rng.integers(0, c, rows) for c in self.card], 1)
        z_sig = np.where(miss[:, self.sig_num], 0.0, z[:, self.sig_num])
        y, p = self._labels(rng, self._logit(z_sig, codes[:, self.sig_cat])
                            + self.intercept(seed))
        cells = np.where(miss, np.nan, self._raw(z))
        cols: Dict[str, np.ndarray] = {"txn_id": np.char.add("t", np.arange(rows).astype(str))}
        cols.update({name: cells[:, j] for j, name in enumerate(self.num_names)})
        cat_labels = np.array([f"k{c:02d}" for c in range(int(self.card.max()))])
        cols.update({name: cat_labels[codes[:, j]] for j, name in enumerate(self.cat_names)})
        cols["tag"] = np.where(y, "bad", "good")
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, "fraud.csv")
        _write_delimited(path, cols)
        meta = os.path.join(out_dir, "meta.names")
        with open(meta, "w") as f:
            f.write("txn_id\n")
        cate = os.path.join(out_dir, "categorical.names")
        with open(cate, "w") as f:
            f.write("\n".join(self.cat_names) + "\n")
        return {"path": path, "meta": meta, "categorical": cate, "rows": rows,
                "pos_rate": float(y.mean()), "bayes_auc": rank_auc(p, y)}

    # ------------------------------------------------------- full planes
    def binned_chunk(self, rows: int, seed: int, chunk: int, n_bins_col: np.ndarray,
                     intercept: float) -> Dict[str, np.ndarray]:
        """One shard in the binned domain.  ``n_bins_col[j]`` = value bins
        of column j as the model set's ColumnConfig has them; index
        ``n_bins_col[j]`` is the missing bin.  Numeric cells fall in
        equal-frequency bins, so a uniform bin index is the binned image
        of a continuous value."""
        rng = fold_seed(seed, 2, chunk)
        nb = np.asarray(n_bins_col, np.uint16)
        u = np.frombuffer(rng.bytes(rows * self.width), np.uint8).reshape(rows, self.width)
        bins = ((u.astype(np.uint16) * nb[None, :]) >> 8).astype(np.uint8)
        m = np.frombuffer(rng.bytes(rows * self.n_num), np.uint8).reshape(rows, self.n_num)
        miss = m < np.uint8(round(self.miss * 256))
        bins[:, :self.n_num] = np.where(miss, nb[None, :self.n_num].astype(np.uint8),
                                        bins[:, :self.n_num])
        z_sig = np.empty((rows, len(self.sig_num)), np.float32)
        nd = NormalDist()
        for k, j in enumerate(self.sig_num):
            n = int(nb[j])
            lut = np.array([nd.inv_cdf((b + 0.5) / n) for b in range(n)] + [0.0], np.float32)
            z_sig[:, k] = lut[bins[:, j]]
        codes = np.stack([np.minimum(bins[:, self.n_num + j], self.card[j] - 1)
                          for j in self.sig_cat], 1).astype(np.int64) \
            if len(self.sig_cat) else np.zeros((rows, 0), np.int64)
        y, _ = self._labels(rng, self._logit(z_sig, codes) + intercept)
        return {"bins": bins, "y": y.astype(np.float32), "w": np.ones(rows, np.float32)}

    def normalised_chunk(self, rows: int, seed: int, chunk: int,
                         intercept: float) -> Dict[str, np.ndarray]:
        """One shard in the normalised (z-scaled, cut at 4) f32 domain:
        missing numeric cells sit at the mean, categoricals take one of
        ``card`` levels."""
        rng = fold_seed(seed, 3, chunk)
        x = rng.standard_normal((rows, self.width), dtype=np.float32)
        np.clip(x, -4.0, 4.0, out=x)
        m = np.frombuffer(rng.bytes(rows * self.n_num), np.uint8).reshape(rows, self.n_num)
        x[:, :self.n_num][m < np.uint8(round(self.miss * 256))] = 0.0
        codes = np.empty((rows, len(self.sig_cat)), np.int64)
        for j in range(self.n_cat):
            c = int(self.card[j])
            code = np.minimum(((x[:, self.n_num + j] + 4.0) * (c / 8.0)).astype(np.int64), c - 1)
            x[:, self.n_num + j] = (code - (c - 1) / 2.0) / max((c * c - 1) / 12.0, 0.25) ** 0.5
            hit = np.nonzero(self.sig_cat == j)[0]
            if len(hit):
                codes[:, hit[0]] = code
        y, _ = self._labels(rng, self._logit(x[:, self.sig_num], codes) + intercept)
        return {"x": x, "y": y.astype(np.float32), "w": np.ones(rows, np.float32)}


def _write_delimited(path: str, cols: Dict[str, np.ndarray]) -> None:
    """Pipe-delimited text with a header; NaN -> empty field.  Cells are
    already rounded to three decimals, so the shortest repr is exact.
    pyarrow writes this ~5x faster than pandas; pandas is the fallback."""
    try:
        import pyarrow as pa
        import pyarrow.csv as pc
    except ImportError:
        import pandas as pd
        pd.DataFrame(cols).to_csv(path, sep="|", index=False, float_format="%.3f", na_rep="")
        return
    table = pa.table({k: pa.array(v, from_pandas=True) for k, v in cols.items()})
    with open(path, "wb") as f:
        f.write(("|".join(cols) + "\n").encode())
        pc.write_csv(table, f, pc.WriteOptions(include_header=False, delimiter="|",
                                               quoting_style="none"))


def json_records(text_path: str, n: int, skip: int = 0) -> Tuple[List[dict], List[str]]:
    """The sample's rows ``skip .. skip+n`` as raw JSON objects of the
    feature columns (numbers as written, three decimals; category strings;
    missing cells absent) and each one serialised."""
    records, texts = [], []
    with open(text_path) as f:
        header = f.readline().rstrip("\n").split("|")
        for _ in range(skip):
            f.readline()
        for _ in range(n):
            line = f.readline()
            if not line:
                break
            rec = {}
            for name, cell in zip(header, line.rstrip("\n").split("|")):
                if name in ("txn_id", "tag") or cell == "":
                    continue
                rec[name] = cell if name.startswith("c") else float(cell)
            records.append(rec)
            texts.append(json.dumps(rec, separators=(",", ":")))
    return records, texts
