"""Rows that go from a shard file to the device without a host array of the
plane's size: the loader's fill threads read them into small host pieces that
are allocated once a load and reused, each filled piece is uploaded and
written into the device plane at its row offset, in place.

The consumer of a key asks for this by handing ``Shards.load_all`` a
:class:`RowLayout` for it; the loader's fill loop is the same for a host
slice and for these pieces (``data/shards.py``).
"""

from __future__ import annotations

import queue
import threading
from functools import partial
from typing import Iterator, List, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs

# bytes of one staging piece (a whole number of rows), two a fill thread.
# Measured on the chip (my chip run, PR 30: 1,572,864 x 432 f32 in 12
# shards, 8 threads, seconds a load): 2 MB 1.24, 4 MB 0.70, 8 MB 0.51,
# 16 MB 0.52, 32 MB 0.72, 64 MB 1.13.  Smaller pieces pay a transfer's
# fixed cost more often; larger ones fault more fresh pages and start
# the first upload later
PIECE_BYTES = 8 << 20


class RowLayout(NamedTuple):
    """How a consumer wants a key's rows on the device: ``sharding`` splits
    rows only (the other axes whole), and zero rows are appended up to a
    multiple of ``multiple``."""
    sharding: jax.sharding.Sharding
    multiple: int


@partial(jax.jit, donate_argnums=0)
def _place(part, piece, row):
    """``piece`` over the rows of ``part`` from ``row`` on, in place
    (``part`` is donated).  Traced once a process for each pair of shapes;
    ``row`` is a traced scalar.  The second result is there for the
    caller to wait on: ``part`` itself is donated to the next call."""
    at = (row,) + (0,) * (part.ndim - 1)
    return jax.lax.dynamic_update_slice(part, piece, at), \
        row + piece.shape[0]


class _Part:
    """The rows ``lo:hi`` of the plane that one device holds."""
    __slots__ = ("lo", "hi", "device", "rows")

    def __init__(self, lo, hi, device, rows):
        self.lo, self.hi, self.device, self.rows = lo, hi, device, rows


class DevicePlane:
    """One key of a resident load on its way to the device.  Every device
    of the layout gets its row range zero-filled once; the fill threads
    take their two staging pieces from :meth:`dest`, and what they fill
    is uploaded to the devices that own those rows and placed there.
    ``staging`` never grows with the plane: two pieces a thread."""

    def __init__(self, rows: int, tail: tuple, dtype: np.dtype,
                 layout: RowLayout, threads: int):
        self.shape = (rows + -rows % layout.multiple,) + tuple(tail)
        self.sharding = layout.sharding
        self._lock = threading.Lock()   # parts' donation chains, counts
        self._parts: List[_Part] = []
        index_of = self.sharding.addressable_devices_indices_map(self.shape)
        for device, index in index_of.items():
            if any(s != slice(None) for s in index[1:]):
                raise ValueError(f"{self.sharding} splits more than rows")
            lo, hi, _ = index[0].indices(self.shape[0])
            self._parts.append(_Part(lo, hi, device, jnp.zeros(
                (hi - lo,) + self.shape[1:], dtype, device=device)))
        row_bytes = max(int(np.prod(tail, dtype=np.int64)) * dtype.itemsize,
                        1)
        self.piece_rows = max(PIECE_BYTES // row_bytes, 1)
        if self.piece_rows > 128:       # offsets on the device's tiles
            self.piece_rows -= self.piece_rows % 128
        self.nbytes = rows * row_bytes
        self.staging = np.empty((2 * threads, self.piece_rows) + tuple(tail),
                                dtype)
        self._free: queue.SimpleQueue = queue.SimpleQueue()
        for t in range(threads):
            self._free.put(self.staging[2 * t:2 * t + 2])
        self.staged_bytes = self.pieces = 0

    def dest(self, lo: int, hi: int, span) -> "_StagedRows":
        """Rows ``lo:hi`` as a destination of the loader's fill; its waits
        for the device are child spans of ``span``, the fill's own."""
        return _StagedRows(self, lo, hi, span)

    def _send(self, piece: np.ndarray, row: int) -> list:
        """``piece`` to every device that holds rows of it; what to wait
        for before its memory is written again."""
        done = []
        for part in self._parts:
            lo, hi = max(row, part.lo), min(row + len(piece), part.hi)
            if lo >= hi:
                continue
            up = jax.device_put(piece[lo - row:hi - row], part.device)
            with self._lock:
                part.rows, token = _place(part.rows, up, lo - part.lo)
            done.append(token)
        with self._lock:
            self.staged_bytes += piece.nbytes
            self.pieces += 1
        return done

    def array(self) -> jax.Array:
        """The plane, every piece placed (the fills have waited)."""
        return jax.make_array_from_single_device_arrays(
            self.shape, self.sharding, [p.rows for p in self._parts])


class _StagedRows:
    """Rows ``lo:hi`` of a :class:`DevicePlane` as the fill sees them:
    pieces to write into, sent on when the fill asks for the next."""

    def __init__(self, plane: DevicePlane, lo: int, hi: int, span):
        self.plane, self.lo, self.hi, self.span = plane, lo, hi, span

    def _wait(self, done: list) -> None:
        """Until the placements ``done`` have read their pieces: on the CPU
        backend a ``device_put`` may alias host memory, on the chip the
        copy is asynchronous; the placement's result covers both."""
        if done:
            with obs.span("data.put").under(self.span):
                for token in done:
                    token.block_until_ready()

    def pieces(self) -> Iterator[np.ndarray]:
        """Staging pieces covering the rows in order.  The caller fills
        the piece it was given before it asks for the next: that is when
        the piece is sent.  A piece is handed out again only after the
        placement that read it is done, and the pair goes back only after
        both are (also when the fill gives up half way)."""
        plane = self.plane
        pair = plane._free.get()
        reading: List[list] = [[], []]
        try:
            for n, at in enumerate(range(self.lo, self.hi, plane.piece_rows)):
                self._wait(reading[n % 2])
                reading[n % 2] = []
                piece = pair[n % 2][:min(plane.piece_rows, self.hi - at)]
                yield piece
                reading[n % 2] = plane._send(piece, at)
        finally:
            try:
                self._wait(reading[0] + reading[1])
            finally:
                plane._free.put(pair)

    def write(self, src: np.ndarray) -> None:
        """The rows from an array that holds them (a decoded member, a
        memmap's slice)."""
        at = 0
        for piece in self.pieces():
            piece[...] = src[at:at + len(piece)]
            at += len(piece)
