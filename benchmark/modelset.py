"""Model sets made by the program's own steps, and full-size planes written
in the shard format ``shifu_tpu/data/shards.py`` reads.

The on-disk formats (``ModelConfig.json``, ``ColumnConfig.json``,
``schema.json`` + ``part-*.npz``, ``tmp/train.progress``, ``EvalScore``) and
``shifu_tpu.cli.main`` are the interface: nothing here imports a trainer.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional

import numpy as np

from .gen import Table

SHARD_ROWS = 131072


def cli(*args: str) -> float:
    """One step through the entry point a user calls; returns its wall."""
    from shifu_tpu.cli import main
    t0 = time.perf_counter()
    rc = main(list(args))
    dt = time.perf_counter() - t0
    if rc != 0:
        raise RuntimeError(f"shifu-tpu {' '.join(args)} returned {rc}")
    return dt


def _edit_json(path: str, fn) -> dict:
    with open(path) as f:
        doc = json.load(f)
    fn(doc)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
    return doc


def set_train(mdir: str, params: Optional[dict] = None, **train_keys) -> dict:
    """Patch ``train`` in ModelConfig.json: ``params`` updates train#params,
    other keys (numTrainEpochs, validSetRate, ...) are set as given."""
    def fn(doc):
        if params:
            doc["train"]["params"] = {**(doc["train"]["params"] or {}), **params}
        doc["train"].update(train_keys)
    return _edit_json(os.path.join(mdir, "ModelConfig.json"), fn)


def make_model_set(work: str, name: str, text: dict, config: dict) -> str:
    """``new -> init -> init -model -> stats -> norm`` on the text sample:
    genuine Model/ColumnConfig at the configuration's columns."""
    tr = config["train"]
    cli("--dir", work, "new", name, "-t", tr["algorithm"])
    mdir = os.path.join(work, name)

    def fn(doc):
        ds = doc["dataSet"]
        ds.update(dataPath=text["path"], dataDelimiter="|", targetColumnName="tag",
                  posTags=["bad"], negTags=["good"], metaColumnNameFile=text["meta"],
                  categoricalColumnNameFile=text["categorical"])
        doc["stats"].update(config["stats"])
        doc["train"].update(baggingNum=1, validSetRate=tr["validSetRate"])
        if isinstance(tr["params"], dict):
            doc["train"]["params"] = dict(tr["params"])
        if "numTrainEpochs" in tr:
            doc["train"]["numTrainEpochs"] = tr["numTrainEpochs"]
        ev = doc["evals"][0]["dataSet"]
        ev.update(dataPath=text["path"], dataDelimiter="|", targetColumnName="tag",
                  posTags=["bad"], negTags=["good"])
    _edit_json(os.path.join(mdir, "ModelConfig.json"), fn)
    cli("--dir", mdir, "init")
    if tr["params"] == "init -model":
        cli("--dir", mdir, "init", "-model")
        with open(os.path.join(mdir, "ModelConfig.json")) as f:
            got = json.load(f)["train"]["params"]
        want = tr["expect_params"]
        if {k: got.get(k) for k in want} != want:
            raise RuntimeError(f"init -model wrote {got}, the configuration expects {want}")
    cli("--dir", mdir, "stats")
    cli("--dir", mdir, "norm")
    return mdir


def clone_model_set(src: str, dst: str) -> str:
    """A second model set with the same configs and column statistics (no
    data, no models): the check's small sample trains here."""
    os.makedirs(os.path.join(dst, "tmp"), exist_ok=True)
    for f in ("ModelConfig.json", "ColumnConfig.json"):
        shutil.copy(os.path.join(src, f), dst)
    return dst


def plane_dir(mdir: str, kind: str) -> str:
    return os.path.join(mdir, "tmp", "CleanedData" if kind == "binned" else "NormalizedData")


def column_bins(mdir: str, schema: dict) -> np.ndarray:
    """Value bins per plane column, from ColumnConfig.json in the plane's
    column order (numeric: len(binBoundary); categorical: len(binCategory))."""
    with open(os.path.join(mdir, "ColumnConfig.json")) as f:
        by_num = {c["columnNum"]: c for c in json.load(f)}
    out = []
    for cn in schema["columnNums"]:
        b = by_num[cn]["columnBinning"]
        cats = b.get("binCategory")
        out.append(len(cats) if cats else len(b["binBoundary"]))
    return np.asarray(out)


def write_planes(mdir: str, table: Table, kind: str, rows: int, seed: int,
                 template_schema: dict, tag: int = 0, keep: bool = False) -> Optional[dict]:
    """Replace the model set's plane with ``rows`` rows drawn from the seed,
    one npz shard per 131,072 rows, written by a few threads.  ``keep``
    returns the plane's arrays as well (the check's small sample)."""
    d = plane_dir(mdir, kind)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    # the norm step's journal pins the shard sizes *it* wrote; these planes
    # are not its, and `train` trusts a plane that has no journal
    journal = os.path.join(mdir, "tmp", "journal", "NORMALIZE.json")
    if os.path.isfile(journal):
        os.remove(journal)
    if len(template_schema["columnNums"]) != table.width:
        raise RuntimeError(f"the model set's plane has {len(template_schema['columnNums'])} "
                           f"columns, the table {table.width}")
    nb = column_bins(mdir, template_schema) if kind == "binned" else None
    intercept = table.intercept(seed)
    sizes = [min(SHARD_ROWS, rows - s) for s in range(0, rows, SHARD_ROWS)]

    def one(i: int):
        chunk = 1000 * tag + i
        part = table.binned_chunk(sizes[i], seed, chunk, nb, intercept) if kind == "binned" \
            else table.normalised_chunk(sizes[i], seed, chunk, intercept)
        np.savez(os.path.join(d, f"part-{i:05d}.npz"), **part)
        return part if keep else None

    with ThreadPoolExecutor(max_workers=min(8, len(sizes))) as pool:
        parts = list(pool.map(one, range(len(sizes))))
    schema = {k: v for k, v in template_schema.items() if not k.startswith("wire")}
    schema.update(numShards=len(sizes), numRows=rows, shardRows=sizes)
    with open(os.path.join(d, "schema.json"), "w") as f:
        json.dump(schema, f)
    os.sync()       # gigabytes of dirty pages written back during the window slow its first jobs
    if keep:
        return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
    return None


def read_schema(mdir: str, kind: str) -> dict:
    with open(os.path.join(plane_dir(mdir, kind), "schema.json")) as f:
        return json.load(f)


def progress_lines(mdir: str) -> List[tuple]:
    """[(train_err, valid_err)] per tree / epoch from tmp/train.progress."""
    out = []
    with open(os.path.join(mdir, "tmp", "train.progress")) as f:
        for line in f:
            m = re.search(r"Train Error: (\S+) Validation Error: (\S+)", line)
            if m:
                out.append((float(m.group(1)), float(m.group(2))))
    return out


def eval_scores(mdir: str, n: int) -> np.ndarray:
    """The first ``n`` mean scores of EvalScore (input row order)."""
    out = []
    with open(os.path.join(mdir, "evals", "Eval1", "EvalScore")) as f:
        col = f.readline().strip().split("|").index("mean")
        for line in f:
            out.append(float(line.split("|")[col]))
            if len(out) == n:
                break
    return np.asarray(out)


def head_of_text(text: dict, rows: int, out_path: str) -> str:
    """The sample's first ``rows`` records (with the header) as a file of
    their own: the ``eval`` step's input."""
    with open(text["path"]) as f, open(out_path, "w") as g:
        for _ in range(rows + 1):
            g.write(f.readline())
    return out_path


def telemetry_counter(mdir: str, name: str) -> float:
    """Sum of one counter over the model set's ``telemetry/trace.jsonl``
    (each step's flush resets the registry)."""
    path = os.path.join(mdir, "telemetry", "trace.jsonl")
    total = 0.0
    if not os.path.isfile(path):
        return total
    with open(path) as f:
        for line in f:
            try:
                doc = json.loads(line)
            except ValueError:
                continue
            if doc.get("name") == name and doc.get("kind", "counter") in ("counter", "metric"):
                total += float(doc.get("value") or 0)
    return total
