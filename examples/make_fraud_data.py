"""Generate the synthetic fraud-style tutorial dataset.

Mirrors the reference's bundled tutorial data shape (pipe-delimited,
mixed numeric/categorical, missing values, a weight column, bad/good
tags) so the quickstart below runs the whole pipeline end-to-end on
data that behaves like the real thing.

    python examples/make_fraud_data.py [out_dir] [n_rows]
"""

import os
import sys

import numpy as np


def make(out_dir: str = ".", n: int = 10000, seed: int = 7) -> str:
    rng = np.random.default_rng(seed)
    amount = rng.lognormal(3.0, 1.2, n)
    velocity = rng.poisson(3, n).astype(float)
    age_days = rng.integers(0, 2000, n).astype(float)
    country = rng.choice(["US", "GB", "DE", "CN", "BR"], n,
                         p=[.5, .15, .15, .1, .1])
    channel = rng.choice(["web", "app", "pos"], n)
    noise = rng.normal(0, 1, n)
    logit = (0.8 * np.log1p(amount) - 0.004 * age_days + 0.35 * velocity
             + (country == "BR") * 1.2 + (channel == "web") * 0.4 - 4.0)
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(int)
    tag = np.where(y == 1, "bad", "good")
    weight = np.round(rng.uniform(0.5, 2.0, n), 3)
    miss = rng.random(n) < 0.05                 # 5% missing amounts
    amount_s = np.round(amount, 4).astype(str)
    amount_s[miss] = ""
    rows = ["txn_id|amount|velocity|age_days|country|channel|noise|weight|tag"]
    for i in range(n):
        rows.append(
            f"t{i}|{amount_s[i]}|{velocity[i]:.0f}|{age_days[i]:.0f}|"
            f"{country[i]}|{channel[i]}|{noise[i]:.5f}|{weight[i]}|{tag[i]}")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "fraud.csv")
    with open(path, "w") as f:
        f.write("\n".join(rows) + "\n")
    with open(os.path.join(out_dir, "meta.names"), "w") as f:
        f.write("txn_id\n")                     # id column = meta, not a feature
    print(f"wrote {n} rows -> {path}")
    return path


def make_wide(out_dir: str, n: int = 131072, n_numeric: int = 64,
              seed: int = 7) -> dict:
    """The tutorial data's wide sibling (``chip_smoke.py``'s input): ``n``
    rows x ``n_numeric`` numeric columns + the two categorical columns, a
    weight column, ~5% missing numeric cells and a learnable logit over
    a known subset of columns.  Numeric cells carry three decimals and
    stay below 1000 in magnitude, so distinct values stay distinct in
    float32 (the chip norms raw records in f32).

    Writes ``fraud_wide.csv`` + ``meta.names`` + ``categorical.names``
    and returns ``{"path", "meta", "categorical", "pos_rate",
    "bayes_auc"}`` — ``bayes_auc`` is the AUC of the TRUE probability
    against the drawn labels, the ceiling any model's AUC floor is set
    against.  Vectorized (pandas writes the file); :func:`make`'s byte
    stream is untouched."""
    import pandas as pd

    rng = np.random.default_rng(seed)
    x = rng.normal(0.0, 1.0, (n, n_numeric))
    x[:, 0::4] = np.exp(0.6 * x[:, 0::4])             # skewed amounts
    x[:, 1::4] = np.round(3.0 + 2.0 * x[:, 1::4])     # count-like
    x[:, 2::4] = 50.0 * x[:, 2::4]                    # wide scale
    x = np.clip(np.round(x, 3), -999.0, 999.0)
    country = rng.choice(["US", "GB", "DE", "CN", "BR"], n,
                         p=[.5, .15, .15, .1, .1])
    channel = rng.choice(["web", "app", "pos"], n)
    # signal: eight numeric columns (standardized, one product term) and
    # both categoricals; the other 56 columns are distractors
    inf = np.arange(0, n_numeric, max(1, n_numeric // 8))[:8]
    z = (x[:, inf] - x[:, inf].mean(0)) / x[:, inf].std(0)
    coef = np.array([0.9, -0.7, 0.6, 0.5, -0.5, 0.4, 0.4, -0.3])[:len(inf)]
    logit = z @ coef + 0.5 * z[:, 0] * z[:, 1] \
        + (country == "BR") * 1.2 + (channel == "web") * 0.4 - 2.6
    p_true = 1 / (1 + np.exp(-logit))
    y = rng.random(n) < p_true
    weight = np.round(rng.uniform(0.5, 2.0, n), 3)
    miss = rng.random((n, n_numeric)) < 0.05

    cols = {"txn_id": np.char.add("t", np.arange(n).astype(str))}
    cells = np.where(miss, np.nan, x)                 # NaN -> empty field
    cols.update({f"n{j:02d}": cells[:, j] for j in range(n_numeric)})
    cols.update(country=country, channel=channel, weight=weight,
                tag=np.where(y, "bad", "good"))
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "fraud_wide.csv")
    pd.DataFrame(cols).to_csv(path, sep="|", index=False,
                              float_format="%.3f", na_rep="")
    meta = os.path.join(out_dir, "meta.names")
    with open(meta, "w") as f:
        f.write("txn_id\n")
    cate = os.path.join(out_dir, "categorical.names")
    with open(cate, "w") as f:
        f.write("country\nchannel\n")
    # rank AUC of the true probability (ties are measure-zero here)
    ranks = np.empty(n)
    ranks[np.argsort(p_true, kind="mergesort")] = np.arange(1, n + 1)
    npos = int(y.sum())
    bayes = (ranks[y].sum() - npos * (npos + 1) / 2) / (npos * (n - npos))
    print(f"wrote {n} rows x {n_numeric} numeric -> {path} "
          f"(pos rate {npos / n:.3f}, bayes AUC {bayes:.4f})")
    return {"path": path, "meta": meta, "categorical": cate,
            "pos_rate": npos / n, "bayes_auc": float(bayes)}


if __name__ == "__main__":
    out = sys.argv[1] if len(sys.argv) > 1 else "."
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 10000
    make(out, n)
