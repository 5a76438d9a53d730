"""Worker for the 2-process multi-host test: each process plays one host
(4 virtual CPU devices), the mesh spans both, and a jitted global reduction
crosses the simulated DCN."""

import os
import sys

pid = int(sys.argv[1])
nproc = int(sys.argv[2])
port = sys.argv[3]

os.environ["JAX_PLATFORMS"] = "cpu"
# force EXACTLY 4 local devices, replacing any inherited count (pytest's
# conftest exports 8)
flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
         if "xla_force_host_platform_device_count" not in f]
flags.append("--xla_force_host_platform_device_count=4")
os.environ["XLA_FLAGS"] = " ".join(flags)

import jax  # noqa: E402

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from shifu_tpu.parallel.mesh import (device_mesh,  # noqa: E402
                                     initialize_distributed,
                                     shard_rows_from_local)

os.environ["SHIFU_COORDINATOR"] = f"localhost:{port}"
os.environ["SHIFU_NUM_PROCESSES"] = str(nproc)
os.environ["SHIFU_PROCESS_ID"] = str(pid)
initialize_distributed()

assert jax.process_count() == nproc
assert len(jax.devices()) == 4 * nproc          # global device set

mesh = device_mesh(n_ensemble=1)
assert mesh.shape == {"ensemble": 1, "data": 4 * nproc}, mesh.shape

# each "host" contributes its own row block (its shard files)
local = (np.arange(16, dtype=np.float32).reshape(4, 4) + 100 * pid)
garr = shard_rows_from_local(mesh, local)
assert garr.shape == (4 * nproc, 4), garr.shape

# a global weighted reduction: the cross-host part of a gradient psum
total = float(jax.jit(lambda a: (a * 2.0).sum())(garr))
expected = 2.0 * sum(float((np.arange(16) + 100 * p).sum())
                     for p in range(nproc))
assert total == expected, (total, expected)

# ensemble axis across hosts: members pin to one host each, data stays on
# the host's own ICI domain
mesh2 = device_mesh(n_ensemble=nproc)
assert mesh2.shape == {"ensemble": nproc, "data": 4}
row = [d.process_index for d in mesh2.devices[pid]]
assert row == [pid] * 4, row                     # one host per member row

# ---- a REAL trainer across the process boundary (VERDICT r3 item 7):
# each host feeds its own row block; the gradient psum crosses the DCN
# every step; both controllers must converge to the SAME weights.
from shifu_tpu.models.nn import NNModelSpec  # noqa: E402
from shifu_tpu.train.nn_trainer import (TrainSettings,  # noqa: E402
                                        train_ensemble)

N, D = 256, 8
rng = np.random.default_rng(0)                  # same draw on both hosts
x_all = rng.normal(size=(N, D)).astype(np.float32)
wvec = rng.normal(size=D).astype(np.float32) / np.sqrt(D)
y_all = (1 / (1 + np.exp(-(x_all @ wvec) * 3))
         > rng.random(N)).astype(np.float32)
half = N // nproc
x_global = shard_rows_from_local(mesh, x_all[pid * half:(pid + 1) * half])
assert x_global.shape == (N, D)
tw = np.full((1, N), 0.8, np.float32)
vw = np.full((1, N), 0.2, np.float32)
res = train_ensemble(x_global, y_all, tw, vw,
                     NNModelSpec(input_dim=D, hidden_nodes=[8],
                                 activations=["tanh"], loss="log"),
                     TrainSettings(optimizer="ADAM", learning_rate=0.05,
                                   epochs=12),
                     mesh=mesh)
assert res.history[-1][0] < res.history[0][0], res.history
checksum = float(sum(np.abs(layer[k]).sum()
                     for layer in res.params[0] for k in ("w", "b")))
print(f"proc {pid}: MULTIHOST-TRAIN weights={checksum:.8f} "
      f"err={res.train_errors[0]:.6f}", flush=True)

# minibatch path too: each controller pads its host copy to the batch
# multiple before the plane's one device_put (no gather of the plane)
res_mb = train_ensemble(x_global, y_all, tw, vw,
                        NNModelSpec(input_dim=D, hidden_nodes=[8],
                                    activations=["tanh"], loss="log"),
                        TrainSettings(optimizer="ADAM", learning_rate=0.05,
                                      epochs=3, batch_size=64),
                        mesh=mesh)
assert np.isfinite(res_mb.train_errors[0])
print(f"proc {pid}: MULTIHOST-MINIBATCH ok", flush=True)

# ---- a STREAMED trainer across hosts: windows shard over the GLOBAL
# data axis (ResidentCache + mega coalescing under 2 controllers); both
# processes must absorb identical forests from the replicated fetches
import json  # noqa: E402
import tempfile  # noqa: E402

from shifu_tpu.data.shards import Shards  # noqa: E402
from shifu_tpu.data.streaming import ShardStream  # noqa: E402
from shifu_tpu.train.dt_trainer import (DTSettings,  # noqa: E402
                                        train_gbt_streamed)

_td_ctx = tempfile.TemporaryDirectory(prefix=f"mh_stream_{pid}_")
td = _td_ctx.name                               # auto-removed at exit
rng_t = np.random.default_rng(17)               # same data on both hosts
tbins = rng_t.integers(0, 8, size=(128, 6)).astype(np.int16)
ty = (rng_t.random(128) < 0.4).astype(np.float32)
np.savez(os.path.join(td, "part-00000.npz"), bins=tbins, y=ty,
         w=np.ones(128, np.float32))
with open(os.path.join(td, "schema.json"), "w") as f:
    json.dump({"columnNums": list(range(6)), "numShards": 1,
               "numRows": 128}, f)
stream_t = ShardStream(Shards.open(td), ("bins", "y", "w"),
                       window_rows=64)
sres = train_gbt_streamed(stream_t, 8, None,
                          DTSettings(n_trees=2, depth=2, loss="log",
                                     learning_rate=0.1), mesh=mesh)
tree_sum = float(sum(np.abs(t.leaf_value).sum() + (t.split_feat >= 0).sum()
                     for t in sres.trees))
print(f"proc {pid}: MULTIHOST-STREAMED trees={tree_sum:.8f}", flush=True)

# ---- stats plane across hosts: chunk rows shard over the GLOBAL data
# axis and the moment/histogram reductions psum across the DCN (the
# reference's up-to-999 stats reducers, MapReducerStatsWorker.java)
from shifu_tpu.config.model_config import BinningMethod  # noqa: E402
from shifu_tpu.ops.binning import NumericAccumulator  # noqa: E402

C = 3
xs = rng.normal(size=(200, C)).astype(np.float32)   # same on both hosts
valid = np.ones((200, C), bool)
tgt = (rng.random(200) < 0.4).astype(np.float32)
acc = NumericAccumulator(n_cols=C, num_buckets=64, mesh=mesh)
acc.update_moments(xs, valid)
acc.finalize_range()
acc.update_histogram(xs, valid, tgt, np.ones(200, np.float32))
bnds, aggs, _, _ = acc.finalize_sketch(BinningMethod.EqualTotal, 4)
assert int(aggs[0][:, :2].sum()) == 200
stats_sum = float(sum(np.sum(np.abs(b[np.isfinite(b)])) for b in bnds))
print(f"proc {pid}: MULTIHOST-STATS bnds={stats_sum:.8f}", flush=True)

print(f"proc {pid}: MULTIHOST-OK total={total}", flush=True)
