"""Partition spill-to-disk under memory pressure.

Reference semantics: core/include/Partition.h:207-214 swapOut/swapIn +
Executor.h:179 evictLRUPartition — partitions beyond the executor memory
budget write their buffers to scratchDir and reload transparently on access.

A Partition's leaves serialize to one .npz file; the MemoryManager tracks
registered partitions via WEAK references (dropped partitions unregister
automatically and their spill files are deleted by a finalizer), keeps byte
accounting incrementally, and evicts LRU past the budget. Host-boxed
fallback values stay in memory (small by the normal-case contract).
"""

from __future__ import annotations

import os
import threading
import uuid
import weakref
from collections import OrderedDict
from dataclasses import dataclass
from typing import Optional

import numpy as np

from ..utils.logging import get_logger
from . import columns as C
from . import tracing, xferstats

log = get_logger("spill")


def _leaves_to_npz_dict(part: C.Partition) -> dict:
    out: dict = {}
    for path, leaf in part.leaves.items():
        key = path.replace("#", "%23")
        if isinstance(leaf, C.NumericLeaf):
            out[f"n!{key}!data"] = leaf.data
            if leaf.valid is not None:
                out[f"n!{key}!valid"] = leaf.valid
        elif isinstance(leaf, C.StrLeaf):
            out[f"s!{key}!bytes"] = leaf.bytes
            out[f"s!{key}!len"] = leaf.lengths
            if leaf.valid is not None:
                out[f"s!{key}!valid"] = leaf.valid
        elif isinstance(leaf, C.NullLeaf):
            out[f"z!{key}!n"] = np.asarray([leaf.n])
        # ObjectLeaf stays in memory (pickling arbitrary objects not worth it)
    return out


def load_leaves_npz(src) -> dict:
    """npz image (path or open binary file) -> leaf dict; the read half of
    _leaves_to_npz_dict. Shared by local spill files and the tuplexfile
    format's remote-scheme reads (io/tuplexfmt.py)."""
    leaves: dict = {}
    with np.load(src) as z:
        names = set(z.files)
        seen: set = set()
        for f in names:
            kind, key, _ = f.split("!", 2)
            if key in seen:
                continue
            path = key.replace("%23", "#")
            if kind == "n":
                leaves[path] = C.NumericLeaf(
                    z[f"n!{key}!data"],
                    z[f"n!{key}!valid"] if f"n!{key}!valid" in names
                    else None)
            elif kind == "s":
                leaves[path] = C.StrLeaf(
                    z[f"s!{key}!bytes"], z[f"s!{key}!len"],
                    z[f"s!{key}!valid"] if f"s!{key}!valid" in names
                    else None)
            elif kind == "z":
                leaves[path] = C.NullLeaf(int(z[f"z!{key}!n"][0]))
            seen.add(key)
    return leaves


class SpilledPartition:
    """Disk image of a partition's array leaves."""

    def __init__(self, path: str, obj_leaves: dict):
        self.path = path
        self.obj_leaves = obj_leaves  # ObjectLeafs kept live

    def load(self) -> dict:
        leaves = load_leaves_npz(self.path)
        leaves.update(self.obj_leaves)
        return leaves

    def delete(self) -> None:
        try:
            os.unlink(self.path)
        except OSError:
            pass


@dataclass
class _Entry:
    ref: "weakref.ref[C.Partition]"
    nbytes: int        # bytes currently resident (0 while spilled)


class MemoryManager:
    """LRU partition eviction against a byte budget (reference:
    Executor::evictLRUPartition + BitmapAllocator pressure)."""

    def __init__(self, budget_bytes: int, scratch_dir: str):
        self.budget = budget_bytes
        self.scratch = os.path.join(scratch_dir, f"spill-{os.getpid()}")
        self._entries: "OrderedDict[int, _Entry]" = OrderedDict()
        self._inmem = 0
        self._lock = threading.Lock()
        self._dead: list[int] = []  # filled by weakref callbacks, no lock
        self._pinned: set[int] = set()  # never evicted (in active use)
        self.swap_out_count = 0
        self.swap_in_count = 0
        self.swapped_bytes = 0

    # ------------------------------------------------------------------
    def register(self, part: C.Partition) -> None:
        with self._lock:
            self._reap_locked()  # BEFORE membership: ids get reused after GC
            pid = id(part)
            if pid in self._entries:
                self._entries.move_to_end(pid)
                return
            nb = part.nbytes()

            # callbacks may fire while WE hold the lock (a strong ref
            # dropped inside eviction): never lock here — just enqueue
            def on_dead(_ref, mm=self, key=pid):
                mm._dead.append(key)  # list.append is atomic

            self._entries[pid] = _Entry(weakref.ref(part, on_dead), nb)
            self._inmem += nb
            self._evict_locked(exclude=pid)

    def touch(self, part: C.Partition) -> None:
        """Mark recently used; swap back in if spilled."""
        with self._lock:
            self._reap_locked()
            pid = id(part)
            if pid in self._entries:
                self._entries.move_to_end(pid)
            if getattr(part, "_spilled", None) is not None:
                self._swap_in_locked(part)

    def pin(self, part: C.Partition) -> None:
        """Exclude from eviction while another thread may touch/register
        (prefetch makes mm calls concurrent: touch-then-use isn't atomic
        across threads). Always pair with unpin."""
        with self._lock:
            self._pinned.add(id(part))
            if getattr(part, "_spilled", None) is not None:
                self._swap_in_locked(part)

    def unpin(self, part: C.Partition) -> None:
        with self._lock:
            self._pinned.discard(id(part))

    def _reap_locked(self) -> None:
        while self._dead:
            key = self._dead.pop()
            e = self._entries.pop(key, None)
            if e is not None:
                self._inmem -= e.nbytes

    # ------------------------------------------------------------------
    def _evict_locked(self, exclude: int = -1) -> None:
        """`exclude`: the entry being registered/loaded RIGHT NOW — even a
        partition bigger than the whole budget must stay resident while its
        caller reads it."""
        if self.budget <= 0:
            return
        for pid, entry in list(self._entries.items()):
            if self._inmem <= self.budget:
                break
            if pid == exclude or pid in self._pinned:
                continue
            part = entry.ref()
            if part is None or entry.nbytes == 0 or \
                    getattr(part, "_spilled", None) is not None:
                continue
            self._swap_out_locked(part, entry)

    def _swap_out_locked(self, part: C.Partition, entry: _Entry) -> None:
        os.makedirs(self.scratch, exist_ok=True)
        path = os.path.join(self.scratch, f"p{uuid.uuid4().hex}.npz")
        with tracing.span("mm:spill", "io") as _sp:
            arrays = _leaves_to_npz_dict(part)
            obj = {p: l for p, l in part.leaves.items()
                   if isinstance(l, C.ObjectLeaf)}
            np.savez(path, **arrays)
            if _sp is not tracing.NOOP:
                _sp.set("rows", part.num_rows).set("bytes", entry.nbytes)
        sp = SpilledPartition(path, obj)
        self.swap_out_count += 1
        self.swapped_bytes += entry.nbytes
        xferstats.bump("spill_bytes", entry.nbytes, tag="swap_out")
        self._inmem -= entry.nbytes
        entry.nbytes = 0
        part._spilled = sp  # type: ignore[attr-defined]
        # orphaned spill files are removed when the partition is GC'd
        part._spill_fin = weakref.finalize(part, sp.delete)  # type: ignore[attr-defined]
        part.leaves = {}
        # a device-resident view pins device memory: a partition under
        # memory pressure must not keep one
        if getattr(part, "device_batch", None) is not None:
            part.device_batch = None
        log.debug("swapped out partition (%d rows) to %s", part.num_rows, path)

    def _swap_in_locked(self, part: C.Partition) -> None:
        sp = part._spilled  # type: ignore[attr-defined]
        with tracing.span("mm:swap-in", "io") as _sp:
            part.leaves = sp.load()
            nb = part.nbytes()
            if _sp is not tracing.NOOP:
                _sp.set("rows", part.num_rows).set("bytes", nb)
        part._spilled = None  # type: ignore[attr-defined]
        sp.delete()
        self.swap_in_count += 1
        entry = self._entries.get(id(part))
        if entry is not None:
            entry.nbytes = nb
        self._inmem += nb
        self._evict_locked(exclude=id(part))

    def ensure_loaded(self, part: C.Partition) -> C.Partition:
        self.touch(part)
        return part

    def resident_bytes(self) -> int:
        """Bytes currently resident across registered partitions — the
        quantity the LRU evictor holds under ``budget``. The job service
        reports it per tenant (each job's runner owns its own manager,
        so this IS the job's resident footprint)."""
        with self._lock:
            self._reap_locked()
            return self._inmem

    def metrics(self) -> dict:
        return {"swap_out": self.swap_out_count, "swap_in": self.swap_in_count,
                "swapped_bytes": self.swapped_bytes}

    def metrics_snapshot(self) -> tuple:
        return (self.swap_out_count, self.swap_in_count, self.swapped_bytes)

    def metrics_delta(self, snap: tuple) -> dict:
        return {"swap_out": self.swap_out_count - snap[0],
                "swap_in": self.swap_in_count - snap[1],
                "swapped_bytes": self.swapped_bytes - snap[2]}
