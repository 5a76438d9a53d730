"""Out-of-core streaming data plane — the ``MemoryDiskFloatMLDataSet``
replacement (reference ``core/dtrain/dataset/MemoryDiskFloatMLDataSet.java:
54-99,315-361``: fill heap to a fraction, spill to disk, chain iterators).

TPU-native shape: the dataset never has to fit anywhere.  A ``ShardStream``
re-batches npz shards into fixed-size row windows (one compiled program shape)
while a background thread prefetches the next shard from disk, so the device
computes while the host reads.  Epoch = one pass over all windows.

Sampling masks cannot be materialized ``[bags, n_rows]`` when n_rows is
unbounded, so ``window_member_masks`` derives every row's bag/validation
assignment STATELESSLY from (seed, member, global row index) via a splitmix64
hash — any window of rows can be masked independently and reproducibly,
replacing the reference's load-time per-record assignment
(``AbstractNNWorker.java:668-716``).
"""

from __future__ import annotations

import os
import queue
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from .shards import Shards


def stream_prefetch_depth(override=None) -> int:
    """Prefetch/pipeline depth for shard streams: explicit override >
    env ``SHIFU_TPU_PREFETCH`` > property ``-Dshifu.stream.prefetch=N``
    > default 2.  Depth bounds both the shard read-ahead queue and the
    prepared-window (H2D double-buffer) queue."""
    if override is not None:
        try:
            return max(0, int(override))
        except (TypeError, ValueError):
            pass
    v = os.environ.get("SHIFU_TPU_PREFETCH")
    if v:
        try:
            return max(0, int(v))
        except ValueError:
            pass
    from ..config import environment
    return max(0, environment.get_int("shifu.stream.prefetch", 2))


def pipeline_depth_for(mesh) -> Optional[int]:
    """Pipelined window prep (background-thread masks + device_put) is
    single-device only: a second thread dispatching programs against a
    multi-device CPU mesh can interleave two collective programs, the
    known XLA:CPU in-process rendezvous deadlock.  None = the stream's
    prefetch depth.  Shared by every streamed plane (trees, varselect,
    genetic wrapper) — per-plane copies had already drifted once."""
    if mesh is not None and getattr(mesh, "size", 1) > 1:
        return 0
    return None


def should_stream(shards, schema: Optional[dict] = None) -> bool:
    """THE resident-vs-streamed decision every plane shares (train NN/WDL,
    varselect sensitivity, genetic wrapper): stream out-of-core when the
    f32 norm plane would not fit ``shifu.train.memoryBudgetBytes``;
    forced either way via ``-Dshifu.train.streaming=on|off``."""
    from ..config import environment
    mode = (environment.get_property("shifu.train.streaming", "auto")
            or "auto").lower()
    if mode in ("on", "true", "force"):
        return True
    if mode in ("off", "false"):
        return False
    schema = schema if schema is not None else getattr(shards, "schema", {})
    budget = environment.get_int("shifu.train.memoryBudgetBytes", 1 << 31)
    width = len(schema.get("outputNames") or []) or 1
    n_rows = schema.get("numRows") or shards.num_rows
    return n_rows * 4 * (width + 2) > budget

# ------------------------------------------------------------ hash uniforms
_U64 = np.uint64


def _splitmix64(z: np.ndarray) -> np.ndarray:
    z = (z + _U64(0x9E3779B97F4A7C15))
    z = (z ^ (z >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> _U64(27))) * _U64(0x94D049BB133111EB)
    return z ^ (z >> _U64(31))


def row_uniform(seed: int, stream: int, idx: np.ndarray) -> np.ndarray:
    """Deterministic uniforms in [0,1) keyed by (seed, stream, row index)."""
    with np.errstate(over="ignore"):
        key = _splitmix64(_U64(seed & 0xFFFFFFFF) * _U64(0x100000001B3)
                          + _U64(stream & 0xFFFFFFFF))
        z = _splitmix64(np.asarray(idx, _U64) ^ key)
    return (z >> _U64(11)).astype(np.float64) * (1.0 / (1 << 53))


def _hash_poisson(lam: float, u: np.ndarray, kmax: int = 16) -> np.ndarray:
    """Poisson(lam) counts via inverse CDF on hash uniforms (lam <= ~4)."""
    out = np.zeros(u.shape, np.float32)
    p = np.exp(-lam)
    cdf = np.full(u.shape, p)
    term = p
    for k in range(1, kmax + 1):
        out += (u >= cdf).astype(np.float32)
        term = term * lam / k
        cdf = cdf + term
    return out


def window_member_masks(idx: np.ndarray, bags: int, *, valid_rate: float,
                        kfold: int = -1, sample_rate: float = 1.0,
                        replacement: bool = False,
                        up_sample_weight: float = 1.0,
                        targets: Optional[np.ndarray] = None,
                        seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """(train_w, valid_w): [bags, len(idx)] row weights for a row window.

    Streaming analogue of ``train.sampling.member_masks``: same semantics
    (k-fold partition / shared validation split + Poisson-or-Bernoulli
    bagging / up-sampling) but every assignment is a pure function of the
    global row index, so windows mask independently.  Stratified validation
    degrades to plain Bernoulli(valid_rate) — exact per-class counts need a
    global pass, which streaming by definition doesn't have.
    """
    idx = np.asarray(idx)
    m = len(idx)
    if kfold and kfold > 1:
        fold = (row_uniform(seed, 101, idx) * kfold).astype(np.int64) % kfold
        valid_w = np.stack([(fold == i).astype(np.float32) for i in range(kfold)])
        train_w = 1.0 - valid_w
    else:
        vmask = row_uniform(seed, 11, idx) < valid_rate
        if bags == 1 and sample_rate >= 1.0 and not replacement:
            bag_w = np.ones((1, m), np.float32)
        else:
            bag_w = np.empty((bags, m), np.float32)
            for b in range(bags):
                u = row_uniform(seed, 1000 + b, idx)
                bag_w[b] = _hash_poisson(sample_rate, u) if replacement \
                    else (u < sample_rate).astype(np.float32)
        train_w = bag_w * (~vmask)[None, :]
        valid_w = np.broadcast_to(vmask.astype(np.float32),
                                  (bags, m)).copy()
    if up_sample_weight != 1.0 and targets is not None:
        train_w = train_w * np.where(targets > 0.5, up_sample_weight,
                                     1.0)[None, :]
    return train_w.astype(np.float32), valid_w.astype(np.float32)


# ----------------------------------------------------------------- windows
@dataclass
class Window:
    """A fixed-size row window.  Arrays are padded to ``rows``; padded rows
    have zero ``w`` (and must be ignored via weights by every consumer)."""
    start: int                       # global index of first (real) row
    n_valid: int                     # real rows (<= rows)
    arrays: Dict[str, np.ndarray]    # each [rows, ...]
    src: Optional[Tuple[int, int]] = None   # (shard idx, row offset) of row 0

    @property
    def rows(self) -> int:
        return len(next(iter(self.arrays.values())))

    @property
    def index(self) -> np.ndarray:
        """Global row indices (padded tail gets past-the-end ids)."""
        return np.arange(self.start, self.start + self.rows)


class ShardStream:
    """Windowed, prefetching iterator over npz shards — with an mmap
    spill-cache fast path for every sweep after the first.

    - ``window_rows`` fixes every emitted window's row count (jit-stable
      shapes; the last window is zero-padded).
    - the FIRST full pass reads npz on a daemon thread (a bounded queue
      ``prefetch`` deep overlaps disk IO with consumption) and spills the
      selected columns into flat raw files (:mod:`shifu_tpu.data.spill`);
      every later pass — including the ResidentCache's per-level tail
      re-streams — is pure ``np.memmap`` slicing: no zip decode, no
      reader thread, no copies until the bytes are consumed.
    - ``keys`` selects which arrays to materialize (e.g. ``("x","y","w")``
      for the NN path, ``("bins","y","w")`` for trees).  Integer columns
      re-emerge from the spill in the compact wire dtype (uint8 for
      <=256 bins) — values identical, 2-4x fewer bytes touched.
    """

    def __init__(self, shards: Shards, keys: Sequence[str],
                 window_rows: int, prefetch: Optional[int] = None,
                 spill: Optional[bool] = None,
                 remainder_multiple: int = 0):
        from .spill import spill_enabled
        assert window_rows > 0
        self.shards = shards
        self.keys = tuple(keys)
        self.window_rows = int(window_rows)
        # shape-stable remainder handling (> 0 enables): the LAST partial
        # window pads to the smallest W/2^k rung (k <= 3, rungs kept
        # multiples of ``remainder_multiple`` — the mesh data-axis size —
        # so sharding still divides) that covers its real rows, instead
        # of the full W.  At most 3 extra static shapes ever exist (one
        # per rung, and a given dataset only produces ONE tail shape), so
        # consumers pay at most one extra compile while ingest.rows_padded
        # drops by up to 8x on the tail.  0 keeps the old full-W pad.
        self.remainder_multiple = int(remainder_multiple)
        self.prefetch = stream_prefetch_depth(prefetch)
        self.spill = spill_enabled() if spill is None else bool(spill)
        self._spill_off = False         # sticky: aborted marker / IO error
        self._spill_rd = None           # validated SpillReader
        self.bytes_read = 0             # host-side total across sweeps
                                        # (always on — guard tests read
                                        # it without telemetry)

    # ------------------------------------------------------ spill plumbing
    def _spill_dir(self) -> str:
        from .spill import spill_dir_for
        return spill_dir_for(self.shards.directory, self.keys)

    def _spill_reader(self):
        if self._spill_rd is not None:
            return self._spill_rd
        # direct-to-wire shard sets ARE a spill: serve them mmap-first,
        # regardless of the spill knob (there are no npz to stream and
        # nothing to write through — the wire is the dataset)
        wire = self.shards.wire_reader(self.keys) \
            if hasattr(self.shards, "wire_reader") else None
        if wire is not None:
            self._spill_rd = wire
            return wire
        if not self.spill or self._spill_off:
            return None
        from .spill import open_spill
        try:
            rd, writable = open_spill(self._spill_dir(), self.keys,
                                      self.shards.source_signature())
        except OSError:
            self._spill_off = True
            return None
        if rd is not None:
            self._spill_rd = rd
        elif not writable:
            self._spill_off = True      # permanent abort marker on disk
        return rd

    def _spill_writer(self):
        """A writer for the cold pass, or None (disabled / already built /
        permanently aborted)."""
        if not self.spill or self._spill_off or self._spill_rd is not None:
            return None
        from .spill import SpillWriter, spill_budget_bytes
        try:
            return SpillWriter(self._spill_dir(), self.keys,
                               self.shards.source_signature(),
                               spill_budget_bytes())
        except OSError:
            self._spill_off = True
            return None

    # background shard reader (cold npz path); the spill write-through
    # happens HERE, off the consumer's critical path
    def _reader(self, q: "queue.Queue", stop: threading.Event,
                start_shard: int, shard_offset: int, writer=None) -> None:
        def put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False
        try:
            for si, part in enumerate(
                    self.shards.iter_shards(start_shard, strict=True)):
                item = {k: part[k] for k in self.keys}
                if writer is not None and not writer.append(item):
                    writer = None             # abandoned; keep streaming
                if si == 0 and shard_offset:
                    item = {k: v[shard_offset:] for k, v in item.items()}
                if not put((start_shard + si, shard_offset if si == 0 else 0,
                            item)):
                    if writer is not None:
                        writer.abort()        # consumer abandoned mid-epoch
                    return
            if writer is not None:
                writer.finish()
            put(None)
        except BaseException as e:  # surface IO errors on the consumer side
            if writer is not None:
                writer.abort()
            put(e)

    def windows(self, start_shard: int = 0, shard_offset: int = 0,
                start_row: int = 0) -> Iterator[Window]:
        """Window the shard sequence.  The three offsets resume mid-dataset
        (the ResidentCache tail: skip fully-cached shard files entirely,
        slice into the first partial one, keep global row ids aligned).
        A committed spill serves the whole call by mmap slicing."""
        rd = self._spill_reader()
        if rd is not None:
            g0 = rd.global_of(start_shard, shard_offset)
            if g0 is not None:
                obs.counter("ingest.spill_hits").inc()
                yield from self._windows_mmap(rd, g0, start_row)
                return
        obs.counter("ingest.spill_misses").inc()
        yield from self._windows_npz(start_shard, shard_offset, start_row)

    def _tail_rows(self, buffered: int) -> int:
        """Padded row count for the final partial window: the smallest
        remainder-ladder rung covering ``buffered`` (see __init__), or
        the full window when the ladder is off / nothing smaller fits."""
        w = self.window_rows
        m = self.remainder_multiple
        if m <= 0 or buffered >= w:
            return w
        rung, r = w, w // 2
        for _ in range(3):
            if r < max(m, buffered) or r % m:
                break
            rung, r = r, r // 2
        return rung

    def _windows_mmap(self, rd, g0: int, start_row: int) -> Iterator[Window]:
        """Serve windows as raw-file slices — the hot path for every sweep
        after the first (src/start bookkeeping identical to the npz path,
        so ResidentCache tail resumes are oblivious to which path ran)."""
        W = self.window_rows
        if rd.rows <= g0:
            return
        mms = {k: rd.memmap(k) for k in self.keys}
        bytes_c = obs.counter("ingest.bytes_read")
        win_c = obs.counter("ingest.windows_emitted")
        rows_c = obs.counter("ingest.rows_emitted")
        pad_c = obs.counter("ingest.rows_padded")
        start, g = start_row, g0
        while g < rd.rows:
            e = min(g + W, rd.rows)
            arrays = {k: np.asarray(mms[k][g:e]) for k in self.keys}
            nv = e - g
            if nv < W:
                rows = self._tail_rows(nv)
                arrays = {k: _pad_rows(a, rows) for k, a in arrays.items()}
                pad_c.inc(rows - nv)
            nb = sum(a.nbytes for a in arrays.values())
            bytes_c.inc(nb)
            self.bytes_read += nb
            win_c.inc()
            rows_c.inc(nv)
            yield Window(start=start, n_valid=nv, arrays=arrays,
                         src=rd.src_of(g))
            start += W
            g += W

    def _windows_npz(self, start_shard: int = 0, shard_offset: int = 0,
                     start_row: int = 0) -> Iterator[Window]:
        writer = self._spill_writer() \
            if (start_shard == 0 and shard_offset == 0) else None
        q: "queue.Queue" = queue.Queue(maxsize=max(1, self.prefetch))
        stop = threading.Event()
        t = threading.Thread(target=self._reader,
                             args=(q, stop, start_shard, shard_offset,
                                   writer),
                             daemon=True)
        t.start()
        try:
            buf: Dict[str, list] = {k: [] for k in self.keys}
            # (shard idx, offset of first unconsumed row, rows left) per
            # buffered source chunk — gives each window its (shard, offset)
            sources: list = []
            buffered = 0
            start = start_row
            W = self.window_rows
            bytes_c = obs.counter("ingest.bytes_read")
            win_c = obs.counter("ingest.windows_emitted")
            rows_c = obs.counter("ingest.rows_emitted")

            def consume(rows: int) -> Tuple[int, int]:
                """Pop ``rows`` rows off the source list; return the (shard,
                offset) of the first popped row."""
                src = (sources[0][0], sources[0][1])
                left = rows
                while left > 0 and sources:
                    si, off, n = sources[0]
                    take = min(left, n)
                    left -= take
                    if take == n:
                        sources.pop(0)
                    else:
                        sources[0] = (si, off + take, n - take)
                return src

            while True:
                item = q.get()
                if isinstance(item, BaseException):
                    raise item
                if item is None:
                    break
                si, off, part = item
                n = len(next(iter(part.values())))
                if n == 0:
                    continue
                for k in self.keys:
                    buf[k].append(part[k])
                sources.append((si, off, n))
                buffered += n
                while buffered >= W:
                    arrays, buf, buffered = _take(buf, W, self.keys)
                    nb = sum(a.nbytes for a in arrays.values())
                    bytes_c.inc(nb)
                    self.bytes_read += nb
                    win_c.inc()
                    rows_c.inc(W)
                    yield Window(start=start, n_valid=W, arrays=arrays,
                                 src=consume(W))
                    start += W
            if buffered:
                arrays, buf, _ = _take(buf, buffered, self.keys)
                rows = self._tail_rows(buffered)
                arrays = {k: _pad_rows(a, rows) for k, a in arrays.items()}
                # padding waste surface for the utilization report: rows
                # the device computes over that carry zero weight
                obs.counter("ingest.rows_padded").inc(rows - buffered)
                nb = sum(a.nbytes for a in arrays.values())
                bytes_c.inc(nb)
                self.bytes_read += nb
                win_c.inc()
                rows_c.inc(buffered)
                yield Window(start=start, n_valid=buffered,
                             arrays=arrays, src=consume(buffered))
        finally:
            # unblock + retire the reader even when the generator is
            # abandoned mid-iteration (jit error, early stop, interrupt);
            # JOIN it so no daemon thread survives into interpreter
            # shutdown (a live thread racing stdio finalization is a
            # "Fatal Python error: _enter_buffered_busy" waiting to happen)
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=5.0)

    def prepared(self, prepare: Callable[["Window"], "PreparedWindow"],
                 start_shard: int = 0, shard_offset: int = 0,
                 start_row: int = 0,
                 depth: Optional[int] = None) -> Iterator["PreparedWindow"]:
        """Pipelined window prep + H2D double-buffering: window assembly
        AND the trainer's ``prepare`` hook (hash masks, host stacking,
        ``jax.device_put``) run on a background thread, ``depth`` windows
        ahead of the consumer — the put for window N+1 is issued while
        window N's executable runs, so the fixed per-put protocol cost
        and host prep overlap device compute instead of serializing with
        it (the TF-sys / sync-SGD input-pipelining prescription).

        ``depth=None`` uses the stream's prefetch depth; ``depth<=0``
        runs inline (multi-device CPU meshes must stay inline: a second
        thread dispatching collective programs can interleave two mesh
        programs, the known XLA:CPU rendezvous deadlock).  Time the
        consumer spends blocked on the queue lands in the
        ``ingest.h2d_wait_seconds`` counter — the ingest stall the
        telemetry report surfaces."""
        depth = self.prefetch if depth is None else int(depth)

        def _prep(win: "Window") -> "PreparedWindow":
            item = prepare(win)
            if getattr(item, "src", None) is None:
                try:
                    item.src = win.src    # tail bookkeeping (ResidentCache)
                except AttributeError:
                    pass
            return item

        if depth <= 0:
            # inline: every second of window fetch + prep IS consumer
            # stall — record it so the report's stall line still reads
            # true on rigs that must prep inline (multi-device CPU mesh)
            wait_c = obs.counter("ingest.h2d_wait_seconds")
            it = self.windows(start_shard, shard_offset, start_row)
            while True:
                t0 = time.perf_counter()
                with obs.span("ingest.window_prep"):
                    win = next(it, None)
                    if win is None:
                        return
                    item = _prep(win)
                wait_c.inc(time.perf_counter() - t0)
                yield item
            return

        q: "queue.Queue" = queue.Queue(maxsize=depth)
        stop = threading.Event()

        def put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker() -> None:
            # each window's assembly+prep runs under an ingest.window_prep
            # span — recorded off the main thread, so the timeline export
            # (obs/timeline) lands them on their own track opposite the
            # consumer's device-compute spans, making the PR 2/6 overlap
            # (or the lack of it) visually auditable
            try:
                for win in self.windows(start_shard, shard_offset,
                                        start_row):
                    with obs.span("ingest.window_prep", window=win.start,
                                  rows=win.n_valid):
                        item = _prep(win)
                    if not put(item):
                        return
                put(None)
            except BaseException as e:
                put(e)

        t = threading.Thread(target=worker, daemon=True,
                             name="shifu-ingest")
        t.start()
        wait_s = 0.0
        try:
            while True:
                t0 = time.perf_counter()
                with obs.span("ingest.h2d_wait"):
                    item = q.get()
                wait_s += time.perf_counter() - t0
                if isinstance(item, BaseException):
                    raise item
                if item is None:
                    break
                yield item
        finally:
            obs.counter("ingest.h2d_wait_seconds").inc(wait_s)
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=5.0)

    @property
    def num_rows(self) -> int:
        return self.shards.num_rows


def _take(buf: Dict[str, list], rows: int, keys: Sequence[str]):
    """Split ``rows`` rows off the buffer front (no copy when aligned)."""
    arrays = {}
    rest: Dict[str, list] = {}
    for k in keys:
        cat = buf[k][0] if len(buf[k]) == 1 else np.concatenate(buf[k])
        arrays[k] = cat[:rows]
        rest[k] = [cat[rows:]] if len(cat) > rows else []
    remaining = sum(len(a) for a in rest[keys[0]])
    return arrays, rest, remaining


def _pad_rows(a: np.ndarray, rows: int) -> np.ndarray:
    if len(a) >= rows:
        return a
    pad = np.zeros((rows - len(a),) + a.shape[1:], a.dtype)
    return np.concatenate([a, pad])


@dataclass
class PreparedWindow:
    """A window after the trainer's ``prepare`` hook — arrays may live on
    device (sharded over a mesh) or host.  ``src`` is filled in by
    ``ShardStream.prepared`` / ``ResidentCache`` from the source window
    (tail resume bookkeeping); hooks need not set it."""
    start: int
    n_valid: int
    rows: int
    index: np.ndarray
    arrays: Dict[str, object]
    resident: bool = False
    src: Optional[Tuple[int, int]] = None

    @property
    def nbytes(self) -> int:
        return int(sum(getattr(a, "nbytes", 0)
                       for a in self.arrays.values()))


class ResidentCache:
    """Two-tier window residency — the ``MemoryDiskFloatMLDataSet.java:54-99``
    memoryFraction design, TPU-shaped: prepared (typically device-resident,
    mesh-sharded) windows fill a byte budget; only the tail past the budget
    re-streams from disk on every subsequent sweep, resuming at the recorded
    (shard, offset) so fully-cached shard files are never re-read.

    With the dataset under budget, a GBT tree's (depth+2) level sweeps cost
    ZERO disk passes after the single warm pass — the round-2 design's
    (depth+2) full re-reads collapse to ~1/forest.  ``disk_passes`` counts
    actual stream traversals for tests/telemetry.

    Window prep runs through ``ShardStream.prepared`` (assembly + masks +
    ``device_put`` pipelined ``pipeline_depth`` windows ahead on a
    background thread); resident windows keep their device buffers — and
    any per-row state the trainer attaches (GBT scores ``f``, RF oob
    votes) — alive across every subsequent sweep.  ``pipeline_depth=0``
    forces inline prep (required on multi-device CPU meshes, see
    ``ShardStream.prepared``)."""

    def __init__(self, stream: "ShardStream", budget_bytes: int,
                 prepare: Callable[[Window], PreparedWindow],
                 pipeline_depth: Optional[int] = None):
        self.stream = stream
        self.budget = int(budget_bytes)
        self.prepare = prepare
        self.pipeline_depth = pipeline_depth
        self.cached: list = []
        self.tail: Optional[Tuple[int, int, int]] = None  # shard, offset, row
        self.disk_passes = 0
        self.tail_sweeps = 0
        self._warm = False

    def _prepared(self, start_shard: int = 0, shard_offset: int = 0,
                  start_row: int = 0) -> Iterator[PreparedWindow]:
        return self.stream.prepared(self.prepare, start_shard, shard_offset,
                                    start_row, depth=self.pipeline_depth)

    def items(self) -> Iterator[PreparedWindow]:
        if not self._warm:
            used = 0
            caching = True
            self.disk_passes += 1
            obs.counter("ingest.disk_passes").inc()
            for item in self._prepared():
                if caching and used + item.nbytes <= self.budget:
                    item.resident = True
                    self.cached.append(item)
                    used += item.nbytes
                elif caching:
                    caching = False
                    self.tail = (item.src[0], item.src[1], item.start) \
                        if item.src else (0, 0, 0)
                yield item
            self._warm = True
        else:
            yield from self.cached
            if self.tail is not None:
                yield from self.tail_items()

    def tail_items(self) -> Iterator[PreparedWindow]:
        """Re-stream ONLY the tail (windows past the resident budget) —
        one disk pass over the spill/npz remainder, prep pipelined like
        the warm pass.  The super-batched tree trainers sweep the
        resident set as a coalesced device block and drive the disk tail
        through this; ``train.tail_sweeps`` counts the tail re-streams
        the schedule actually paid (the disk-passes guard tests and the
        ``analysis --telemetry`` tail stall line read it)."""
        if not self._warm:
            raise RuntimeError("tail_items() before the warm pass — "
                               "iterate items() once first")
        if self.tail is None:
            return
        self.disk_passes += 1
        self.tail_sweeps += 1
        obs.counter("ingest.disk_passes").inc()
        obs.counter("train.tail_sweeps").inc()
        sh, off, row = self.tail
        yield from self._prepared(start_shard=sh, shard_offset=off,
                                  start_row=row)

    @property
    def resident_rows(self) -> int:
        return sum(it.n_valid for it in self.cached)

    @property
    def warmed(self) -> bool:
        """True once the first full sweep has classified every window as
        resident or tail — ``tail`` is only meaningful after this."""
        return self._warm


def auto_window_rows(row_bytes: int, budget_bytes: int,
                     multiple: int = 8, lo: int = 1024,
                     hi: int = 1 << 22, n_rows: Optional[int] = None) -> int:
    """Window size from a device-memory budget (the reference's
    ``guagua.data.memoryFraction`` analogue, ``AbstractNNWorker.java:
    479-496``): as many rows as fit, clamped and rounded to ``multiple``.

    ``n_rows`` (when the schema knows it) caps the window at the dataset —
    windows pad to their full static shape, so without the cap a small
    dataset under a big budget computes over millions of padded rows per
    sweep (measured 2800x waste: 1500 rows in a 4.19M-row window)."""
    rows = int(budget_bytes // max(row_bytes, 1))
    if n_rows:
        hi = min(hi, n_rows + (-n_rows) % multiple)
        lo = min(lo, hi)
    rows = max(lo, min(rows, hi))
    return max(multiple, rows - rows % multiple)


def stream_window_rows(row_bytes: int, data_size: int, shards) -> int:
    """THE window-geometry recipe for every streamed trainer (NN / WDL /
    trees): the ``shifu.train.windowRows`` override or the budget-derived
    auto size, capped at the dataset (see :func:`auto_window_rows`) and
    rounded up to the mesh data axis.  One implementation — per-trainer
    copies drifted (different rounding directions, a missing dataset cap
    that cost a 2800x padded-row waste)."""
    from ..config import environment
    budget = environment.get_int("shifu.train.memoryBudgetBytes", 1 << 31)
    n_rows = (shards.schema.get("numRows") if hasattr(shards, "schema")
              else None) or getattr(shards, "num_rows", None)
    wr = environment.get_int("shifu.train.windowRows", 0) or \
        auto_window_rows(row_bytes, budget, multiple=data_size,
                         n_rows=n_rows)
    wr += (-wr) % data_size
    return max(data_size, wr)


MaskFn = Callable[[np.ndarray, np.ndarray], Tuple[np.ndarray, np.ndarray]]


def mask_fn_from_settings(bags: int, *, valid_rate: float, kfold: int = -1,
                          sample_rate: float = 1.0, replacement: bool = False,
                          up_sample_weight: float = 1.0,
                          seed: int = 0) -> MaskFn:
    """Bind sampling settings into a ``(index, targets) -> (train_w,
    valid_w)`` window mask function for the streamed trainers."""
    def fn(idx: np.ndarray, targets: np.ndarray):
        return window_member_masks(
            idx, bags, valid_rate=valid_rate, kfold=kfold,
            sample_rate=sample_rate, replacement=replacement,
            up_sample_weight=up_sample_weight, targets=targets, seed=seed)
    return fn
