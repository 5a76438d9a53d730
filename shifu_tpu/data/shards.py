"""Shard IO for the materialized norm/clean datasets."""

from __future__ import annotations

import json
import logging
import os
import struct
import zipfile
import zlib
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from dataclasses import dataclass, field
from typing import (TYPE_CHECKING, Callable, Dict, Iterator, List, Mapping,
                    NamedTuple, Optional, Sequence, Tuple, TypeVar)

import numpy as np
from numpy.lib import format as npf

if TYPE_CHECKING:
    from .staging import RowLayout

log = logging.getLogger(__name__)

T = TypeVar("T")

# sidecar manifest of per-shard row counts (written on first scan; the
# norm step writes the counts straight into schema.json as "shardRows",
# so materialized datasets never scan at all)
ROWS_SIDECAR = ".shard_rows.json"


def bins_wire_dtype(n_bins: int) -> np.dtype:
    """The ONE compact storage/wire dtype policy for bin ids 0..n_bins-1:
    norm shards, the spill cache and the host→device transfer all use it
    (the reference stores worker rows as short[] bin ids,
    ``DTWorker.java:100`` — f32/int32 on the wire is pure waste)."""
    if n_bins <= 1 << 8:
        return np.dtype(np.uint8)
    if n_bins <= 1 << 16:
        return np.dtype(np.uint16)
    return np.dtype(np.int32)


def _npy_header(f) -> Tuple[tuple, bool, np.dtype]:
    """(shape, fortran_order, dtype) of the npy stream ``f`` stands at the
    start of; ``f`` is left at the array's first byte."""
    if npf.read_magic(f) == (1, 0):
        return npf.read_array_header_1_0(f)
    return npf.read_array_header_2_0(f)


def _npz_rows(path: str) -> int:
    """Row count of one npz shard WITHOUT decoding any array: read the
    npy header of one member through the zip directory.  Falls back to a
    full load on any format surprise."""
    try:
        with zipfile.ZipFile(path) as z:
            names = z.namelist()
            name = "y.npy" if "y.npy" in names else names[0]
            with z.open(name) as f:
                shape = _npy_header(f)[0]
                return int(shape[0]) if shape else 0
    except Exception:
        return int(len(np.load(path)["y"]))


# the direct fill reads and checksums a member in pieces of this size, so
# the CRC pass finds the bytes it has just read still in the cache
_FILL_PIECE = 4 << 20


class _Member(NamedTuple):
    """One array of an npz shard, as the zip directory and its npy header
    describe it.  ``start`` is the file offset of the member's npy
    header when the array can be read straight into its destination (a
    *stored* member in C order with a plain dtype — what ``np.savez``
    writes); None sends the member through ``np.load``."""
    shape: tuple
    dtype: np.dtype
    start: Optional[int] = None
    head: int = 0              # length of the npy header
    crc: int = 0               # the directory's CRC-32 of the member


class _ShardPlan(NamedTuple):
    path: str
    rows: int
    members: Dict[str, _Member]

    @property
    def layout(self) -> Dict[str, tuple]:
        """What every shard of a set must agree on, key by key."""
        return {k: (m.dtype, m.shape[1:]) for k, m in self.members.items()}


def _plan_npz(path: str) -> _ShardPlan:
    """Sizes before bytes: the shape, dtype and whereabouts of every
    array of one npz shard, read from the zip directory and the npy
    headers alone.  A member whose length disagrees with its header is a
    ``BadZipFile`` here, before a byte of the plane is allocated."""
    members: Dict[str, _Member] = {}
    with open(path, "rb") as f, zipfile.ZipFile(f) as z:
        for info in z.infolist():
            with z.open(info) as m:
                shape, fortran, dtype = _npy_header(m)
                head = m.tell()
            start = None
            if info.compress_type == zipfile.ZIP_STORED and not fortran \
                    and not dtype.hasobject:
                nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
                if info.file_size != head + nbytes:
                    raise zipfile.BadZipFile(
                        f"{path}: member {info.filename} holds "
                        f"{info.file_size} bytes, its header describes "
                        f"{head + nbytes}")
                f.seek(info.header_offset)
                local = f.read(30)
                if local[:4] != b"PK\x03\x04":
                    raise zipfile.BadZipFile(
                        f"{path}: no local header for {info.filename}")
                start = info.header_offset + 30 + sum(
                    struct.unpack("<HH", local[26:30]))
            key = info.filename[:-4] if info.filename.endswith(".npy") \
                else info.filename
            members[key] = _Member(tuple(shape), dtype, start, head,
                                   info.CRC)
    rows = {m.shape[0] if m.shape else None for m in members.values()}
    if len(rows) != 1 or None in rows:
        raise ValueError(f"{path}: arrays of unequal or no length "
                         f"{ {k: m.shape for k, m in members.items()} }")
    return _ShardPlan(path, int(rows.pop()), members)


class _HostRows:
    """A key's rows of one shard in the host plane, as a destination of
    the fill: written where they stay.  The other destination is
    ``staging._StagedRows``, pieces on their way to the device."""

    def __init__(self, rows: np.ndarray):
        self.rows = rows

    def pieces(self) -> Iterator[np.ndarray]:
        yield self.rows

    def write(self, src: np.ndarray) -> None:
        self.rows[...] = src


def _fill_npz(plan: _ShardPlan, dest: Dict[str, "_HostRows"]) -> bool:
    """Read one npz shard into ``dest`` (its rows of the plane, key by
    key).  Stored members go file -> destination in one copy, checked
    against the directory's CRC-32 as ``zipfile`` would; the others are
    decoded by ``np.load`` and written.  True when every member went
    straight."""
    decode = [k for k, m in plan.members.items() if m.start is None]
    with open(plan.path, "rb") as f:
        for k, m in plan.members.items():
            if m.start is None:
                continue
            f.seek(m.start)
            crc = zlib.crc32(f.read(m.head))
            for rows in dest[k].pieces():
                view = rows.reshape(-1).view(np.uint8)
                for a in range(0, len(view), _FILL_PIECE):
                    piece = view[a:a + _FILL_PIECE]
                    if f.readinto(piece) != len(piece):
                        raise zipfile.BadZipFile(
                            f"{plan.path}: member {k} ends early")
                    crc = zlib.crc32(piece, crc)
            if crc != m.crc:
                raise zipfile.BadZipFile(
                    f"{plan.path}: bad CRC-32 for member {k}")
    if decode:
        with np.load(plan.path) as z:
            for k in decode:
                dest[k].write(z[k])
    return not decode


def _fill_width(n_shards: int) -> int:
    """Threads of a resident load: what the machine gives this process,
    no more than the shards, and no more than saturate a host's memory."""
    return max(1, min(n_shards, len(os.sched_getaffinity(0)), 8))


def _close_holes(out: Dict[str, np.ndarray], cum: np.ndarray,
                 holes: List[int]) -> Dict[str, np.ndarray]:
    """The rare branch of a resident load: shards ``holes`` were
    quarantined after the plane was sized, so every later shard's rows
    move down over them and the plane ends where the good rows do."""
    keep = sorted(set(range(len(cum) - 1)) - set(holes))
    for a in out.values():
        at = 0
        for i in keep:
            n = int(cum[i + 1] - cum[i])
            if at != cum[i]:
                a[at:at + n] = a[cum[i]:cum[i + 1]]
            at += n
    return {k: a[:at] for k, a in out.items()}


class _Quarantine:
    """The ``shifu.data.badThreshold`` rule of one pass over a shard set:
    an undecodable shard is skipped, counted and logged as long as the
    quarantined fraction stays under the threshold."""

    ERRORS = (OSError, ValueError, zipfile.BadZipFile)

    def __init__(self, n_shards: int, strict: bool = False):
        from ..config import environment
        self.threshold = 0.0 if strict else \
            environment.get_float("shifu.data.badThreshold", 0.0)
        self.n_shards = n_shards
        self.count = 0

    def skip(self, path: str, e: BaseException) -> None:
        """Called while ``e`` is being handled: re-raises it unless the
        rule lets the shard at ``path`` go."""
        if self.threshold <= 0:
            raise
        from .. import obs
        self.count += 1
        # quarantine is the rare branch by definition —
        # bounded by shifu.data.badThreshold
        obs.counter("data.quarantined_shards").inc()  # shifu-lint: disable=telemetry-guard
        log.warning("quarantined undecodable shard %s: %s", path, e)
        if self.count / max(self.n_shards, 1) > self.threshold:
            from ..config.errors import ErrorCode, ShifuError
            raise ShifuError(
                ErrorCode.ERROR_BAD_DATA_THRESHOLD,
                f"{self.count}/{self.n_shards} shards "
                f"quarantined exceeds shifu.data.badThreshold="
                f"{self.threshold}; last: {path} ({e})") from e


class _WireView:
    """SpillReader facade over the TAIL of a wire plane starting at shard
    ``base`` — memmaps/prefix-sums rebase so ShardStream's window and
    cursor bookkeeping are oblivious to where the view starts (the
    ``from_row`` refresh cursor, which slices npz file lists the same
    way)."""

    def __init__(self, rd, base_shard: int):
        self._rd = rd
        self._g0 = int(rd.cum[base_shard])
        self.rows = rd.rows - self._g0
        self.shard_rows = list(rd.shard_rows[base_shard:])
        self.cum = (np.asarray(rd.cum[base_shard:]) - self._g0).astype(
            np.int64)

    def memmap(self, key: str):
        return self._rd.memmap(key)[self._g0:]

    def global_of(self, shard: int, offset: int) -> Optional[int]:
        if not 0 <= shard < len(self.shard_rows):
            return None
        g = int(self.cum[shard]) + int(offset)
        return g if 0 <= g <= self.rows else None

    def src_of(self, g: int):
        si = int(np.searchsorted(self.cum, g, side="right") - 1)
        return si, int(g - self.cum[si])


@dataclass
class Shards:
    directory: str
    schema: dict
    files: List[str]
    _shard_rows: Optional[List[int]] = field(default=None, repr=False,
                                             compare=False)
    # wire mode (schema "wire"): shards live as flat spill raw files, no
    # npz at all; _wire_base is the from_row cursor in shard units
    _wire_base: int = field(default=0, repr=False, compare=False)
    _wire_rd: Optional[object] = field(default=None, repr=False,
                                       compare=False)

    @classmethod
    def open(cls, directory: str) -> "Shards":
        with open(os.path.join(directory, "schema.json")) as f:
            schema = json.load(f)
        files = sorted(os.path.join(directory, f) for f in os.listdir(directory)
                       if f.endswith(".npz"))
        return cls(directory, schema, files)

    @property
    def is_wire(self) -> bool:
        return bool(self.schema.get("wire"))

    def wire_reader(self, keys: Optional[Sequence[str]] = None):
        """A SpillReader(-like) over the wire plane, or None when this
        shard set is npz-backed.  ``keys`` names what the caller will
        read — any subset of the wire's keys is served from the same raw
        files.  A schema that claims wire over an invalid/torn spill is
        a coded error (there are no npz to fall back to): re-run norm."""
        if not self.is_wire:
            return None
        wire_keys = list(self.schema.get("wireKeys") or [])
        if keys is not None and not set(keys) <= set(wire_keys):
            raise ValueError(
                f"wire plane in {self.directory} carries {wire_keys}, "
                f"caller asked for {list(keys)}")
        if self._wire_rd is None:
            from .spill import open_spill, wire_dir
            d = wire_dir(self.directory, wire_keys)
            rd, _ = open_spill(d, wire_keys,
                               self.schema.get("wireSignature"))
            if rd is None:
                from ..config.errors import ErrorCode, ShifuError
                raise ShifuError(
                    ErrorCode.ERROR_INPUT_NOT_FOUND,
                    f"{self.directory}: schema says direct-to-wire but "
                    f"the wire spill under {d} is missing, torn or "
                    "stale — re-run `norm` (or set "
                    "-Dshifu.norm.wireOnly=false to materialize npz)")
            self._wire_rd = rd
        rd = self._wire_rd
        return _WireView(rd, self._wire_base) if self._wire_base else rd

    def _read_shard(self, i: int, read: Callable[[], T], what: str) -> T:
        """The contract of every read of shard ``i``, whoever asks and on
        whichever thread: the fault hook sees the shard once an attempt
        and transient IO errors ride the retry ladder."""
        from .. import faults
        from ..ioutil import io_retry
        path = self.directory if self.is_wire else self.files[i]

        def attempt():
            faults.fire("shards", "shard", i, path=path)
            return read()
        return io_retry(attempt, what, path)

    @staticmethod
    def _wire_rows(rd, i: int, keys) -> Dict[str, np.ndarray]:
        s, e = int(rd.cum[i]), int(rd.cum[i + 1])
        return {k: np.asarray(rd.memmap(k)[s:e]) for k in keys}

    def iter_shards(self, start: int = 0,
                    strict: bool = False) -> Iterator[Dict[str, np.ndarray]]:
        """Decode shards in order.  Opens ride the transient-IO retry
        ladder; with ``shifu.data.badThreshold`` > 0 an undecodable shard
        is quarantined (skipped + counted, provenance logged) as long as
        the quarantined fraction stays under the threshold.  ``strict``
        disables quarantine — the streaming window planes index rows by
        shard position and cannot tolerate a silently missing shard.
        Wire-mode planes serve the same per-shard dicts as mmap slices
        (consumers cannot tell which backing they got)."""
        if self.is_wire:
            rd = self.wire_reader()
            keys = list(self.schema.get("wireKeys") or [])
            for i in range(start, len(rd.shard_rows)):
                yield self._read_shard(
                    i, partial(self._wire_rows, rd, i, keys),
                    "wire shard read")
            return
        bad = _Quarantine(len(self.files), strict)
        for i, f in enumerate(self.files[start:], start=start):
            try:
                yield self._read_shard(i, lambda f=f: dict(np.load(f)),
                                       "shard decode")
            except _Quarantine.ERRORS as e:
                bad.skip(f, e)

    def _plan_all(self, bad: _Quarantine) -> List[Optional[_ShardPlan]]:
        """Every shard's sizes, None where the shard was quarantined for
        a directory or a header that cannot be read."""
        from ..ioutil import io_retry
        if self.is_wire:
            rd = self.wire_reader()
            members = {k: _Member(rd.memmap(k).shape, rd.memmap(k).dtype)
                       for k in self.schema.get("wireKeys") or []}
            return [_ShardPlan(self.directory, int(r), members)
                    for r in rd.shard_rows]
        plans: List[Optional[_ShardPlan]] = []
        for f in self.files:
            try:
                plans.append(io_retry(partial(_plan_npz, f),
                                      "shard open", f))
            except _Quarantine.ERRORS as e:
                bad.skip(f, e)
                plans.append(None)
        return plans

    def load_all(self, on_device: Optional[Mapping[str, "RowLayout"]] = None
                 ) -> Dict[str, np.ndarray]:
        """The whole set as one resident plane: each key's array is
        allocated once at its final size and every shard is read straight
        into its row slice, several shards at a time.  Quarantine as in
        :meth:`iter_shards`; the bytes are in the returned arrays when
        this returns.

        A key that ``on_device`` names comes back as a ``jax.Array`` in the
        layout given, zero rows appended: its consumer wants it on the
        device only, so its rows go file -> staging piece -> device
        (``data/staging.py``) and no host array of its size is made."""
        from .. import obs
        on_device = on_device or {}
        with obs.span("data.load") as load_sp:
            with obs.span("data.alloc"):
                bad = _Quarantine(self.n_shards, strict=self.is_wire)
                plans = self._plan_all(bad)
                first = next((p for p in plans if p is not None), None)
                if first is None:
                    raise FileNotFoundError(f"no shards in {self.directory}")
                for p in plans:
                    # an error of the set, not of a shard: never quarantined
                    if p is not None and p.layout != first.layout:
                        raise ValueError(
                            f"{p.path}: arrays {p.layout} disagree with "
                            f"{first.path}: {first.layout}")
                cum = np.cumsum([0] + [p.rows if p else 0 for p in plans])
                todo = [i for i, p in enumerate(plans) if p is not None]
                threads = _fill_width(len(todo))
                out = {k: np.empty((int(cum[-1]),) + shape, dtype)
                       for k, (dtype, shape) in first.layout.items()
                       if k not in on_device}
                staged = {}
                if on_device:
                    from .staging import DevicePlane
                    staged = {k: DevicePlane(int(cum[-1]), shape, dtype,
                                             on_device[k], threads)
                              for k, (dtype, shape) in first.layout.items()
                              if k in on_device}
            rd = self.wire_reader()

            def fill(i: int) -> bool:
                lo, hi = int(cum[i]), int(cum[i + 1])
                dest = {k: staged[k].dest(lo, hi, read_sp) if k in staged
                        else _HostRows(out[k][lo:hi]) for k in first.layout}
                if rd is None:
                    return _fill_npz(plans[i], dest)
                for k, rows in self._wire_rows(rd, i, dest).items():
                    dest[k].write(rows)
                return True

            direct, holes = 0, []
            with obs.span("data.read") as read_sp, \
                    ThreadPoolExecutor(threads, "shard-fill") as pool:
                read_sp.set(bytes=sum(a.nbytes for a in (*out.values(),
                                                         *staged.values())))
                reads = [pool.submit(self._read_shard, i, partial(fill, i),
                                     "shard decode") for i in todo]
                try:
                    for i, r in zip(todo, reads):
                        try:
                            direct += r.result()
                        except _Quarantine.ERRORS as e:
                            bad.skip(plans[i].path, e)
                            holes.append(i)
                except BaseException:
                    pool.shutdown(cancel_futures=True)
                    raise
            out.update((k, p.array()) for k, p in staged.items())
            if holes:
                # rare: the rows placed so far come down again and the
                # consumer gets a host array, as if it had not asked
                out = _close_holes(
                    {k: np.array(out[k])[:int(cum[-1])]
                     if k in staged else out[k] for k in first.layout},
                    cum, holes)
            load_sp.set(bytes=sum(a.nbytes for a in out.values()),
                        shards=self.n_shards, direct=direct,
                        threads=threads,
                        staged_bytes=sum(p.staged_bytes
                                         for p in staged.values()),
                        staging_bytes=sum(p.staging.nbytes
                                          for p in staged.values()),
                        pieces=sum(p.pieces for p in staged.values()))
            return {k: out[k] for k in first.layout}

    def _sidecar_sig(self) -> List[List]:
        return [[os.path.basename(f), os.path.getsize(f)]
                for f in self.files]

    @property
    def shard_rows(self) -> List[int]:
        """Per-shard row counts without decoding shards: schema
        ``shardRows`` (norm writes it), else the sidecar manifest, else a
        one-time npy-header scan persisted back to the sidecar."""
        if self._shard_rows is not None:
            return self._shard_rows
        sr = self.schema.get("shardRows")
        if isinstance(sr, list) and (len(sr) == len(self.files)
                                     or self.is_wire):
            self._shard_rows = [int(x) for x in sr]
            return self._shard_rows
        if self.is_wire:               # schema missing counts: manifest
            self._shard_rows = [int(x)
                                for x in self.wire_reader().shard_rows]
            return self._shard_rows
        side = os.path.join(self.directory, ROWS_SIDECAR)
        sig = self._sidecar_sig()
        try:
            with open(side) as f:
                d = json.load(f)
            if d.get("source") == sig and len(d.get("rows", [])) == \
                    len(self.files):
                self._shard_rows = [int(x) for x in d["rows"]]
                return self._shard_rows
        except (OSError, ValueError):
            pass
        rows = [_npz_rows(f) for f in self.files]
        try:                       # best effort: dir may be read-only
            tmp = side + f".tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump({"source": sig, "rows": rows}, f)
            os.replace(tmp, side)
        except OSError:
            pass
        self._shard_rows = rows
        return rows

    @property
    def num_rows(self) -> int:
        return sum(self.shard_rows)

    @property
    def n_shards(self) -> int:
        """Shard count.  Wire planes have no npz files, so ``len(files)``
        is always 0 there — every consumer comparing or iterating shard
        counts must go through here."""
        return len(self.shard_rows) if self.is_wire else len(self.files)

    def from_row(self, row: int) -> "Shards":
        """A view of this shard set starting at the shard containing
        global row ``row`` — the refresh loop's data-window cursor
        (shard-aligned, rounded DOWN so no row is ever skipped).  A
        cursor at/past the end keeps the LAST shard: with no new data
        the freshest window is still the right thing to train on."""
        if row <= 0 or self.n_shards == 0:
            return self
        rows = self.shard_rows
        cum, k = 0, len(rows) - 1
        for i, r in enumerate(rows):
            if cum + r > row:
                k = i
                break
            cum += r
        kept = [int(x) for x in rows[k:]]
        schema = dict(self.schema)
        if "shardRows" in schema:
            schema["shardRows"] = list(kept)
        if "numRows" in schema:
            schema["numRows"] = int(sum(kept))
        view = Shards(self.directory, schema, list(self.files[k:]))
        view._shard_rows = kept
        view._wire_base = self._wire_base + k
        view._wire_rd = self._wire_rd
        return view

    def source_signature(self) -> List[List]:
        """[(name, size, mtime_ns)] identity of the shard set — the spill
        cache's staleness check (re-running norm rewrites files and
        invalidates any spill built over them).  Wire planes pin the
        schema's wire signature instead (re-running norm rewrites it)."""
        if self.is_wire:
            return [["wire", self.schema.get("wireSignature")]]
        out = []
        for f in self.files:
            st = os.stat(f)
            out.append([os.path.basename(f), st.st_size, st.st_mtime_ns])
        return out
