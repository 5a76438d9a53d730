"""Device mesh substrate — the Guagua-BSP replacement.

The reference's distributed backbone is a Guagua master/worker BSP loop on
YARN (workers compute local gradients/histograms, master sums and broadcasts;
``NNMaster.java:240-286``, ``TrainModelProcessor.java:661-1029``).  Here that
whole stack collapses into SPMD under ``jax.jit`` over a ``Mesh``:

- the ``data`` axis shards rows (the worker shards); gradient aggregation is
  the ``psum`` XLA inserts for replicated-param grads — the master's
  accumulate step, but on ICI instead of ZooKeeper/Netty;
- the ``ensemble`` axis shards bagging/grid-search members (the reference's
  N parallel YARN jobs, ``TrainModelProcessor.java:684-945``) — members train
  simultaneously as one vmapped program, sharded across devices.
- multi-host: after :func:`initialize_distributed`, ``device_mesh()`` spans
  the fleet (jax.devices() is global, host-major), so the data axis keeps a
  host's rows on its own ICI domain and only psum combines cross DCN; with
  n_ensemble = n_hosts each member pins to one host.

Quorum/straggler logic (97% + 2s timeout) has no analogue: the mesh is
synchronous.  Fail-over maps to checkpoint/restore instead.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import numpy as np


def device_mesh(n_ensemble: int = 1,
                devices: Optional[Sequence] = None) -> "jax.sharding.Mesh":
    """Build a 2D ``(ensemble, data)`` mesh over the available devices.

    The ensemble axis gets ``gcd(n_devices, n_ensemble)`` devices (never more
    than there are members to train); the rest go to data parallelism.  With
    one ensemble member this degenerates to a pure data-parallel layout.
    """
    import jax
    from jax.sharding import Mesh

    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    e = math.gcd(n, max(1, n_ensemble))
    grid = np.asarray(devs).reshape(e, n // e)
    return Mesh(grid, ("ensemble", "data"))


def pad_rows(n: int, multiple: int) -> int:
    """Rows to add so n divides the data-axis extent."""
    r = n % multiple
    return 0 if r == 0 else multiple - r


def shard_chunk_rows(mesh, *arrays):
    """Device-put per-row chunk arrays (1D [R] or 2D [R, C]) with rows
    sharded over the mesh ``data`` axis, zero-padded so every shard is
    equal-sized (shard_mapped kernels need that; zero rows are invalid/
    weightless by construction at every call site).  Returns the device
    arrays plus a live-row bool mask marking real rows — ``None`` mask
    (and plain single-device arrays) when ``mesh`` is None or its data
    axis is 1.  This is the stats/eval-plane row scatter, the counterpart
    of the trainers' ``_shard_rows`` (reference: each Guagua/MR worker
    reads its own input split, ``ShifuInputFormat``)."""
    import jax.numpy as jnp

    ds = int(mesh.shape["data"]) if mesh is not None else 1
    if ds <= 1:
        return tuple(jnp.asarray(a) for a in arrays) + (None,)
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    n = arrays[0].shape[0]
    pad = pad_rows(n, ds)
    live = np.ones(n, bool)          # padded below like every other array
    out = []
    for a in list(arrays) + [live]:
        a = np.asarray(a)
        if pad:
            a = np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
        spec = P("data") if a.ndim == 1 else P("data", None)
        out.append(jax.device_put(a, NamedSharding(mesh, spec)))
    return tuple(out)


# ---------------------------------------------------- one process per chip
def refuse_children_on_chip(what: str) -> None:
    """Guard for the modes that start one JAX process per worker
    (``serve --replicas``).  A chip belongs to one process at a time:
    the second process to touch it fails or hangs, and a child pushed
    onto the CPU instead would be a CPU process counted as a chip
    replica.  Until those modes drive every chip from one process
    (ROADMAP D6) they run on a CPU backend only; on a ``tpu`` backend
    this raises a CODED error before anything is spawned."""
    import jax

    if jax.default_backend() == "tpu":
        from ..config.errors import ErrorCode, ShifuError
        raise ShifuError(
            ErrorCode.ERROR_ONE_PROCESS_PER_CHIP,
            f"{what} starts one JAX process per worker with no device "
            f"assignment; on this {len(jax.devices())}-chip host run one "
            "process (e.g. `serve` without --replicas) or set "
            "JAX_PLATFORMS=cpu for a CPU rehearsal")


# ------------------------------------------------------------- multi-host
def initialize_distributed(coordinator: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Multi-host bootstrap — the reference's Guagua/ZooKeeper coordination
    role (``GuaguaConstants`` zk wiring, ``TrainModelProcessor.java``
    cluster submit): after this, ``jax.devices()`` is the GLOBAL device set
    across hosts, a ``device_mesh`` spans them, and XLA routes collectives
    over ICI within a host and DCN across hosts.

    Args default from SHIFU_COORDINATOR / SHIFU_NUM_PROCESSES /
    SHIFU_PROCESS_ID (set by the launcher, one process per host).

    Coordinator connect rides the same bounded exponential-backoff+jitter
    ladder as :func:`ioutil.io_retry` (``shifu.io.retries`` attempts,
    ``shifu.io.retryBaseMs`` base; counter ``dcn.connect_retries``) —
    a controller restarted into a live job retries while the coordinator
    re-admits it, and an exhausted ladder raises a CODED error instead
    of hanging the launcher.
    """
    import os
    import random
    import time

    coordinator = coordinator or os.environ.get("SHIFU_COORDINATOR")
    if coordinator is None:
        return      # single-host run: stays a true no-op (no jax import)
    import jax
    if num_processes is None:
        num_processes = int(os.environ["SHIFU_NUM_PROCESSES"])
    if process_id is None:
        process_id = int(os.environ["SHIFU_PROCESS_ID"])
    from ..config import environment
    attempts = max(0, environment.get_int("shifu.io.retries", 3)) + 1
    base = environment.get_int("shifu.io.retryBaseMs", 50) / 1000.0
    for attempt in range(attempts):
        try:
            jax.distributed.initialize(coordinator,
                                       num_processes=num_processes,
                                       process_id=process_id)
            return
        except (OSError, RuntimeError, ValueError) as e:
            # jaxlib surfaces connect/handshake failures as RuntimeError
            # (XlaRuntimeError subclasses it); ValueError covers a
            # malformed address.  A ladder that ends still raises CODED.
            if attempt + 1 >= attempts:
                from ..config.errors import ErrorCode, ShifuError
                raise ShifuError(
                    ErrorCode.ERROR_DCN_CONNECT,
                    f"coordinator {coordinator} (process "
                    f"{process_id}/{num_processes}) after {attempts} "
                    f"attempt(s): {e}") from e
            from .. import obs
            # retry ladder only spins on coordinator weather — the
            # factory lookup is as cold as the backoff sleep
            obs.counter("dcn.connect_retries").inc()  # shifu-lint: disable=telemetry-guard
            delay = base * (2 ** attempt) * (1.0 + random.random())
            import logging
            logging.getLogger(__name__).warning(
                "jax.distributed.initialize(%s) failed (attempt %d/%d, "
                "retrying in %.0f ms): %s", coordinator, attempt + 1,
                attempts, delay * 1000, e)
            time.sleep(delay)


def shard_rows_from_local(mesh, local_rows: "np.ndarray"):
    """Build the GLOBAL row-sharded array from THIS host's row block — the
    multi-host data feed (each host reads its own shard files, reference
    worker-split role of ``ShifuInputFormat``).  Rows concatenate in
    process order; the per-host block must divide the host's share of the
    data axis."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = P("data") if local_rows.ndim == 1 else P("data", None)
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, spec), local_rows)
