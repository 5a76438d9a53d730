"""The generators: the same seed gives the same data, any seed up to 2^32 is
taken, and a seed never changes a shape or the work of a window."""

import json
import os

import numpy as np
import pytest

from benchmark import loadgen
from benchmark.gen import Table, json_records

SEEDS = [0, 1, 2 ** 31, 2 ** 32 - 1]
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def table():
    with open(os.path.join(HERE, "configs", "gbt-fraud.json")) as f:
        spec = json.load(f)["table"]
    return Table({**spec, "numeric_columns": 24, "categorical_columns": 6,
                  "signal_numeric": 6, "signal_categorical": 2})


@pytest.mark.parametrize("seed", SEEDS)
def test_planes_repeat_and_keep_their_shape(table, seed):
    nb = np.concatenate([np.full(table.n_num, 64), table.card])
    ic = table.intercept(seed)
    a = table.binned_chunk(2048, seed, 0, nb, ic)
    b = table.binned_chunk(2048, seed, 0, nb, ic)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert a["bins"].shape == (2048, 30) and a["bins"].dtype == np.uint8
    assert (a["bins"] <= nb[None, :]).all()
    assert (a["bins"][:, table.n_num:] < table.card[None, :]).all()   # no missing category
    x = table.normalised_chunk(2048, seed, 3, ic)
    y = table.normalised_chunk(2048, seed, 3, ic)
    assert np.array_equal(x["x"], y["x"]) and x["x"].shape == (2048, 30)
    assert x["x"].dtype == np.float32 and np.abs(x["x"]).max() <= 4.0
    other = table.binned_chunk(2048, seed, 1, nb, ic)
    assert not np.array_equal(a["bins"], other["bins"])


def test_seeds_differ_and_the_configuration_does_not(table):
    nb = np.concatenate([np.full(table.n_num, 64), table.card])
    planes = [table.binned_chunk(512, s, 0, nb, 0.0)["bins"] for s in SEEDS]
    assert len({p.tobytes() for p in planes}) == len(SEEDS)
    again = Table({"numeric_columns": 24, "categorical_columns": 6, "positive_rate": 0.035,
                   "missing_rate": 0.25, "cardinality_range": [2, 64], "signal_numeric": 6,
                   "signal_categorical": 2, "table_seed": 2019})
    assert np.array_equal(again.card, table.card) and np.array_equal(again.coef, table.coef)


def test_text_sample_and_json_records(table, tmp_path):
    info = table.write_text(str(tmp_path), 300, 2 ** 32 - 1)
    with open(info["path"]) as f:
        lines = f.read().splitlines()
    assert len(lines) == 301
    header = lines[0].split("|")
    assert header[0] == "txn_id" and header[-1] == "tag" and len(header) == 32
    assert 0.0 <= info["pos_rate"] <= 0.2
    records, texts = json_records(info["path"], 5)
    assert len(records) == 5 and json.loads(texts[0]) == records[0]
    assert all(k.startswith(("n", "c")) for k in records[0])
    assert "" not in records[0].values()                    # missing cells are absent
    again = table.write_text(str(tmp_path / "b"), 300, 2 ** 32 - 1)
    with open(again["path"]) as f:
        assert f.read().splitlines() == lines


TRAFFIC = {"rate_per_s": 40.0, "connections": 4, "record_pool": 64,
           "sizes": [{"share": 0.6, "lo": 1, "hi": 1},
                     {"share": 0.3, "lo": 2, "hi": 16, "spacing": "uniform"},
                     {"share": 0.1, "lo": 17, "hi": 256, "spacing": "log"}]}


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    plans = [loadgen.build_requests(TRAFFIC, 10.0, s, 64) for s in SEEDS]
    sizes = [sorted(r["records"] for r in p) for p in plans]
    assert max(len(s) for s in sizes) - min(len(s) for s in sizes) <= 8   # the cut at the window's end
    full = sorted(loadgen.size_multiset(TRAFFIC["sizes"], 400))
    assert full[0] == 1 and full[-1] <= 256 and full.count(1) == 240
    assert 9.0 < sum(full) / 400 < 16.0                     # the mix's mean, ~12
    orders = {tuple(r["records"] for r in p) for p in plans}
    assert len(orders) == len(SEEDS)
    again = loadgen.build_requests(TRAFFIC, 10.0, SEEDS[2], 64)
    assert again == plans[2]
    for p in plans:
        due = [r["due"] for r in p]
        assert due == sorted(due) and due[-1] < 10.0


def test_bursts_keep_the_mean_rate():
    import random
    t = dict(TRAFFIC, arrivals={"kind": "bursts", "on_ms": 50, "off_ms": 200})
    due = loadgen.arrival_times(t, 400, random.Random(1))
    assert 8.0 < due[-1] < 12.0
    assert all((x % 0.25) <= 0.05 + 1e-9 for x in due)      # only inside the on-phases


def test_body_is_json(tmp_path):
    body = loadgen.body_of(['{"a":1}', '{"b":2}'], 1, 3)
    assert json.loads(body) == {"records": [{"b": 2}, {"a": 1}, {"b": 2}]}
