"""Streamed, mask-batched variable-selection plane (ops/sensitivity +
dvarsel streaming): parity with the seed per-column loop, whole-block
onehot freezing, -inf out-of-plane ranking, single-fetch host-sync guard,
streamed genetic wrapper, vectorized pareto/correlation pruning."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import jax

from shifu_tpu.data.shards import Shards
from shifu_tpu.data.streaming import ShardStream
from shifu_tpu.models.nn import NNModelSpec, init_params
from shifu_tpu.ops import sensitivity as sens
from shifu_tpu.parallel.mesh import device_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_shards(td, arrays, shard_rows=700):
    n = len(next(iter(arrays.values())))
    d = arrays["x"].shape[1]
    k = 0
    for s in range(0, n, shard_rows):
        e = min(s + shard_rows, n)
        np.savez(os.path.join(td, f"part-{k:05d}.npz"),
                 **{key: a[s:e] for key, a in arrays.items()})
        k += 1
    with open(os.path.join(td, "schema.json"), "w") as f:
        json.dump({"outputNames": [f"c{i}" for i in range(d)],
                   "columnNums": list(range(d)),
                   "numShards": k, "numRows": n}, f)
    return Shards.open(td)


@pytest.fixture
def sens_data(rng):
    n, d = 3000, 24
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.random(n) < 0.3).astype(np.float32)
    return x, y


@pytest.mark.parametrize("hidden", [[8], [8, 4], []])
def test_streamed_matches_per_column_loop(tmp_path, sens_data, hidden):
    """Resident inputs: streamed mask-batched SE/ST MSEs match the seed's
    per-column loop within f32 accumulation tolerance, and the resulting
    top-k SELECTIONS are identical (incl. 0-hidden LR heads and deeper
    nets — the rank-k first-layer shortcut must stay exact)."""
    x, y = sens_data
    d = x.shape[1]
    spec = NNModelSpec(input_dim=d, hidden_nodes=hidden,
                       activations=["tanh"] * max(1, len(hidden)))
    params = init_params(jax.random.PRNGKey(0), spec)
    masks = sens.mask_matrix(d, [[i] for i in range(16)])
    mse_ref, base_ref = sens.per_column_scores(spec, params, x, y, masks)

    shards = _write_shards(str(tmp_path), {"x": x, "y": y})
    # window 1024 does not divide 3000: the padded tail must not leak
    stream = ShardStream(shards, ("x", "y"), 1024)
    mse, base, n_rows = sens.streamed_sensitivity(
        stream, spec, params, masks, mesh=device_mesh(), mask_batch=5)
    assert n_rows == len(y)
    np.testing.assert_allclose(mse, mse_ref, rtol=3e-5, atol=1e-6)
    np.testing.assert_allclose(base, base_ref, rtol=3e-5)
    # identical selections for both SE (mse - base) and ST (scaled)
    k = 6
    assert set(np.argsort(-(mse - base))[:k]) \
        == set(np.argsort(-(mse_ref - base_ref))[:k])


def test_onehot_blocks_freeze_whole(tmp_path, sens_data):
    """A candidate's onehot feature block freezes as ONE unit: the mask
    matrix sets every index of the block, and the streamed scores equal
    the per-column loop freezing the same whole block."""
    x, y = sens_data
    d = x.shape[1]
    blocks = [[0], [1, 2, 3], [4, 5], [6]]
    masks = sens.mask_matrix(d, blocks)
    assert masks.shape == (4, d)
    assert list(np.flatnonzero(masks[1])) == [1, 2, 3]
    assert masks.sum() == 7

    spec = NNModelSpec(input_dim=d, hidden_nodes=[6], activations=["tanh"])
    params = init_params(jax.random.PRNGKey(1), spec)
    mse_ref, base_ref = sens.per_column_scores(spec, params, x, y, masks)
    shards = _write_shards(str(tmp_path), {"x": x, "y": y})
    mse, base, _ = sens.streamed_sensitivity(
        ShardStream(shards, ("x", "y"), 1536), spec, params, masks,
        mesh=device_mesh(), mask_batch=3)
    np.testing.assert_allclose(mse, mse_ref, rtol=3e-5, atol=1e-6)


def test_out_of_plane_scores_minus_inf():
    """Candidates absent from the trained model's feature plane score
    -inf (never selectable), in-plane candidates get SE/ST transforms."""
    from shifu_tpu.config.model_config import FilterBy
    from shifu_tpu.pipeline.varselect import _scores_from_mse

    cands = [SimpleNamespace(columnNum=i) for i in range(4)]
    mse = np.array([0.30, 0.20])
    se = _scores_from_mse(cands, [0, 2], mse, 0.25, FilterBy.SE)
    assert se[0] == pytest.approx(0.05)
    assert se[2] == pytest.approx(-0.05)
    assert se[1] == float("-inf") and se[3] == float("-inf")
    st = _scores_from_mse(cands, [0, 2], mse, 0.25, FilterBy.ST)
    assert st[0] == pytest.approx(0.05 / 0.25)
    # -inf candidates rank strictly last under both transforms
    assert min(se[0], se[2]) > se[1]


def test_single_fetch_and_program_count(tmp_path, sens_data):
    """Host-sync guard: the whole streamed job fetches ONCE, and issues
    exactly ceil(C/B) mask-batch programs per window."""
    from shifu_tpu import obs

    x, y = sens_data
    d = x.shape[1]
    spec = NNModelSpec(input_dim=d, hidden_nodes=[4], activations=["tanh"])
    params = init_params(jax.random.PRNGKey(0), spec)
    C, B = 11, 4                                  # ceil(11/4) = 3 batches
    masks = sens.mask_matrix(d, [[i] for i in range(C)])
    shards = _write_shards(str(tmp_path), {"x": x, "y": y})
    n_windows = -(-len(y) // 1024)
    obs.reset_for_tests()
    obs.set_enabled(True)
    try:
        sens.streamed_sensitivity(
            ShardStream(shards, ("x", "y"), 1024), spec, params, masks,
            mesh=device_mesh(), mask_batch=B)
        reg = obs.get_registry()
        assert reg.counter("varsel.host_syncs").value == 1
        assert reg.counter("varsel.mask_batches").value \
            == n_windows * -(-C // B)
        # both passes observed every window
        assert reg.counter("varsel.windows").value == 2 * n_windows
    finally:
        obs.reset_for_tests()


def test_genetic_streamed_recovers_xor(tmp_path):
    """The streamed genetic wrapper (fitness = minibatch scans over
    prepared windows, one [P,2] fetch per generation) ranks the XOR pair
    first — an interaction a filter method cannot see.  XOR has no
    marginal signal, so the search can only stumble on the pair: seed 3
    draws it into generation 0 (numpy's stream alone decides that, no
    float does), and what is pinned is that the streamed fitness then
    separates it decisively and the credit makes it the top two.  The
    body runs in a child with a time limit of its own:
    ``helpers/genetic_streamed_child.py`` says why."""
    rng = np.random.default_rng(3)
    n, d = 2000, 6
    x = rng.normal(size=(n, d)).astype(np.float32)
    xor = (x[:, 0] > 0) ^ (x[:, 1] > 0)
    y = (rng.random(n) < 1 / (1 + np.exp(-3.0 * np.where(xor, 1, -1)))) \
        .astype(np.float32)
    _write_shards(str(tmp_path), {"x": x, "y": y,
                                  "w": np.ones(n, np.float32)},
                  shard_rows=512)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tests", "helpers", "genetic_streamed_child.py"),
         str(tmp_path),
         json.dumps({"n_select": 2, "population": 12, "generations": 4,
                     "epochs": 40, "seed": 3})],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
    doc = json.loads(p.stdout.strip().splitlines()[-1])
    scores, history = doc["scores"], doc["history"]
    top2 = sorted(scores, key=scores.get, reverse=True)[:2]
    assert set(top2) == {"0", "1"}, scores
    assert history[-1]["best"] < 0.5 < history[-1]["mean"], history
    assert history[-1]["best"] <= history[0]["best"] + 1e-6


def test_pareto_vectorized_matches_reference(rng):
    """The broadcast domination matrix reproduces the seed's per-point
    O(n^2) Python scan exactly."""
    from shifu_tpu.pipeline.varselect import pareto_front_ranks

    def reference(ks, iv):
        n = len(ks)
        remaining = np.arange(n)
        ranks = np.zeros(n, int)
        r = 0
        while len(remaining):
            k, v = ks[remaining], iv[remaining]
            dominated = np.zeros(len(remaining), bool)
            for i in range(len(remaining)):
                dominated[i] = np.any((k >= k[i]) & (v >= v[i]) &
                                      ((k > k[i]) | (v > v[i])))
            front = remaining[~dominated]
            ranks[front] = r
            remaining = remaining[dominated]
            r += 1
        return ranks

    for n in (1, 2, 17, 100):
        ks = rng.random(n)
        iv = rng.random(n)
        # include ties: duplicated points must co-rank
        if n > 4:
            ks[3], iv[3] = ks[1], iv[1]
        np.testing.assert_array_equal(pareto_front_ranks(ks, iv),
                                      reference(ks, iv))


def test_correlation_prune_vectorized(tmp_path):
    """Matrix-row masking keeps the seed semantics: drop the lower-KS
    member of any pair above the threshold; columns missing from the
    matrix always survive."""
    from shifu_tpu.pipeline.varselect import VarSelectProcessor

    names = ["a", "b", "c", "d"]
    mat = np.eye(4)
    mat[0, 1] = mat[1, 0] = 0.95       # a-b highly correlated
    mat[2, 3] = mat[3, 2] = 0.10
    corr = tmp_path / "correlation.csv"
    with open(corr, "w") as f:
        f.write("," + ",".join(names) + "\n")
        for i, nm in enumerate(names):
            f.write(nm + "," + ",".join(f"{v:.4f}" for v in mat[i]) + "\n")

    def col(name, ks):
        return SimpleNamespace(columnName=name,
                               columnStats=SimpleNamespace(ks=ks))

    proc = VarSelectProcessor.__new__(VarSelectProcessor)
    proc.paths = SimpleNamespace(correlation_path=str(corr))
    cols = [col("a", 0.9), col("b", 0.8), col("c", 0.7), col("d", 0.6),
            col("zz_not_in_matrix", 0.5)]
    vs = SimpleNamespace(correlationThreshold=0.8)
    kept, dropped = proc._correlation_prune(cols, vs)
    assert [c.columnName for c in kept] == ["a", "c", "d",
                                           "zz_not_in_matrix"]
    assert dropped == 1
