"""Child of ``test_varselect_stream.py::test_genetic_streamed_recovers_xor``:
the streamed genetic wrapper over a shard directory the parent wrote, on 4
virtual CPU devices, its scores and history as one JSON line.

Why a child, and why 4 devices.  The wrapper launches epochs x windows
chained programs a generation with ONE fetch at its end.  On the suite's
8-device mesh (ensemble 4 x data 2) each carries an all-reduce over the
data axis, and under a loaded host XLA:CPU's in-process rendezvous then
stops with half its participants (``rendezvous.cc``: "Termination
timeout ... of 40 seconds exceeded") and aborts the interpreter — 16 of
36 runs beside five busy neighbours, 0 of 40 alone (PR 29).  With 4
devices the program's own mesh is ensemble 4 x data 1: no collective, no
rendezvous.  The child keeps an abort, should XLA find another, to one
failed test instead of a lost xdist worker.
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
         if "xla_force_host_platform_device_count" not in f]
flags.append("--xla_force_host_platform_device_count=4")
os.environ["XLA_FLAGS"] = " ".join(flags)

from shifu_tpu.data.shards import Shards  # noqa: E402
from shifu_tpu.data.streaming import ShardStream  # noqa: E402
from shifu_tpu.train.dvarsel import (WrapperSettings,  # noqa: E402
                                     genetic_varselect_streamed)

shard_dir, settings = sys.argv[1], json.loads(sys.argv[2])
shards = Shards.open(shard_dir)
d = len(shards.schema["outputNames"])
scores, history = genetic_varselect_streamed(
    ShardStream(shards, ("x", "y", "w"), 1024),
    {ci: [ci] for ci in range(d)}, WrapperSettings(**settings))
print(json.dumps({"scores": {str(k): float(v) for k, v in scores.items()},
                  "history": history}))
