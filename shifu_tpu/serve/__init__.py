"""Online serving plane — sustained-traffic scoring with latency SLOs.

The reference's production surface scores one record at a time
(``IndependentNNModel`` / ``IndependentTreeModel`` behind a thread pool,
~1.5k rows/s/worker measured — BASELINE.md); this plane applies the
large-fused-graph argument to inference: concurrent single-record
requests coalesce into a handful of PRE-COMPILED padded-bucket device
launches, so the per-request cost is one queue append, not one tracing +
dispatch round trip.

Modules:

- :mod:`scorer`  — :class:`AOTScorer`: the modelset's ensemble pinned in
  HBM once, ``lower()→compile()`` one executable per batch bucket
  (no per-request tracing; the recompile sentinel
  from :mod:`shifu_tpu.obs.costs` polices shape churn);
- :mod:`batcher` — :class:`MicroBatcher`: request queue + deadline
  batcher that coalesces requests into the smallest covering bucket of a
  geometric ladder (``-Dshifu.serve.buckets``), padding the remainder and
  flushing on ``-Dshifu.serve.maxDelayMs`` so p99 is bounded at low load
  and throughput wins at high load;
- :mod:`registry` — :class:`ModelRegistry`: live models keyed by
  modelset with atomic hot-swap (build + warm the new scorer fully, then
  journal-style promote) so a retrain replaces the live model without
  dropping requests;
- :mod:`server`  — :class:`ServeServer` + the ``shifu-tpu serve`` CLI
  entry: heartbeats from :mod:`shifu_tpu.obs.health` (carrying queue
  depth + the live SLO summary), optional stdlib HTTP front-end
  (``POST /score``, ``GET /healthz``, ``GET /slo``, ``POST /swap``);
- :mod:`transform` — :class:`FusedTransform`: the offline norm pipeline
  (binning, WoE/zscore maps, missing handling) compiled as a jnp
  prelude INSIDE the scorer executable, so ``POST /score`` accepts raw
  ``{field: value}`` records bit-identical to the offline norm+eval
  path;
- :mod:`router`  — :class:`ServeRouter` + ``shifu-tpu serve
  --replicas N``: N worker processes behind a health-/SLO-aware
  balancing front with requeue-on-replica-death and coordinated
  no-mixed-window fleet hot-swap (``-Dshifu.serve.canaryFrac`` commits
  an explicit canary slice instead).

Observability: per-request tracing (head-sampled at
``-Dshifu.serve.traceSampleRate``, or forced by an ``X-Shifu-Trace``
header) decomposes each sampled request into queue-wait / deadline-wait
/ pad / launch / device spans with batch fan-in links (see
:mod:`batcher`), and every completion feeds the live SLO plane
(:mod:`shifu_tpu.obs.slo`: sliding-window quantiles, burn-rate alerts
against ``-Dshifu.serve.sloP99Ms`` / ``-Dshifu.serve.sloAvailability``).
"""

from .batcher import MicroBatcher, Ticket                     # noqa: F401
from .registry import ModelRegistry                           # noqa: F401
from .router import ServeRouter, run_fleet                    # noqa: F401
from .scorer import (AOTScorer, bucket_ladder,                # noqa: F401
                     covering_bucket, infer_dims,
                     serve_recompile_count)
from .server import ServeServer, max_delay_s                  # noqa: F401
from .transform import FusedTransform                         # noqa: F401

__all__ = [
    "AOTScorer", "bucket_ladder", "covering_bucket", "infer_dims",
    "serve_recompile_count", "MicroBatcher", "Ticket", "ModelRegistry",
    "ServeServer", "max_delay_s", "FusedTransform", "ServeRouter",
    "run_fleet",
]
