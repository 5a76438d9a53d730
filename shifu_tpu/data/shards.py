"""Shard IO for the materialized norm/clean datasets."""

from __future__ import annotations

import json
import logging
import os
import zipfile
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np

log = logging.getLogger(__name__)

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


def _npz_rows(path: str) -> int:
    """Row count of one npz shard WITHOUT decoding any array: read the
    npy header of one member through the zip directory.  Falls back to a
    full load on any format surprise."""
    try:
        from numpy.lib import format as npf
        with zipfile.ZipFile(path) as z:
            names = z.namelist()
            name = "y.npy" if "y.npy" in names else names[0]
            with z.open(name) as f:
                ver = npf.read_magic(f)
                if ver == (1, 0):
                    shape, _, _ = npf.read_array_header_1_0(f)
                else:
                    shape, _, _ = npf.read_array_header_2_0(f)
                return int(shape[0]) if shape else 0
    except Exception:
        return int(len(np.load(path)["y"]))


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

    def _iter_wire(self, start: int) -> Iterator[Dict[str, np.ndarray]]:
        from .. import faults
        from ..ioutil import io_retry
        rd = self.wire_reader()
        keys = list(self.schema.get("wireKeys") or [])
        for i in range(start, len(rd.shard_rows)):
            def _load(i=i):
                faults.fire("shards", "shard", i, path=self.directory)
                s, e = int(rd.cum[i]), int(rd.cum[i + 1])
                return {k: np.asarray(rd.memmap(k)[s:e]) for k in keys}
            yield io_retry(_load, "wire shard read", self.directory)

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
        from .. import faults, obs
        from ..config import environment
        from ..ioutil import io_retry
        if self.is_wire:
            yield from self._iter_wire(start)
            return
        bad_threshold = 0.0 if strict else \
            environment.get_float("shifu.data.badThreshold", 0.0)
        quarantined = 0
        for i, f in enumerate(self.files[start:], start=start):
            def _load(f=f, i=i):
                faults.fire("shards", "shard", i, path=f)
                return dict(np.load(f))
            try:
                yield io_retry(_load, "shard decode", f)
            except (OSError, ValueError, zipfile.BadZipFile) as e:
                if bad_threshold <= 0:
                    raise
                quarantined += 1
                # quarantine is the rare branch by definition —
                # bounded by shifu.data.badThreshold
                obs.counter("data.quarantined_shards").inc()  # shifu-lint: disable=telemetry-guard
                log.warning("quarantined undecodable shard %s: %s", f, e)
                if quarantined / max(len(self.files), 1) > bad_threshold:
                    from ..config.errors import ErrorCode, ShifuError
                    raise ShifuError(
                        ErrorCode.ERROR_BAD_DATA_THRESHOLD,
                        f"{quarantined}/{len(self.files)} shards "
                        f"quarantined exceeds shifu.data.badThreshold="
                        f"{bad_threshold}; last: {f} ({e})") from e

    def load_all(self) -> Dict[str, np.ndarray]:
        from .. import obs
        with obs.span("data.load"):
            parts = []
            shards = self.iter_shards()
            for i in range(self.n_shards):
                # a quarantined shard is skipped inside the iterator: the
                # span then covers it and the next good one
                with obs.span("data.shard_decode", shard=i) as sp:
                    part = next(shards, None)
                    if part is None:
                        break
                    sp.set(rows=len(next(iter(part.values()))),
                           bytes=sum(a.nbytes for a in part.values()))
                parts.append(part)
            if not parts:
                raise FileNotFoundError(f"no shards in {self.directory}")
            with obs.span("data.concat") as sp:
                out = {k: np.concatenate([p[k] for p in parts])
                       for k in parts[0]}
                sp.set(bytes=sum(a.nbytes for a in out.values()))
            return out

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
