"""Columnar host memory layout + host<->device staging.

This replaces the reference's row-format Partition blocks
(reference: core/include/Partition.h:38-85, utils/include/Serializer.h:104-138)
with a TPU-first columnar layout:

  * every logical column is decomposed into fixed-shape leaf arrays
    (FlattenedTuple analog — reference: codegen/include/FlattenedTuple.h:49-57):
      - numeric leaves: one array [N]
      - str leaves:     uint8 bytes [N, W] zero-padded + int32 lengths [N]
      - Option adds a validity bool [N]
      - nested tuples flatten to dotted paths ("col.0.1")
  * a partition covers a contiguous range of original row positions; rows that
    do NOT conform to the normal-case schema keep their slot (placeholder
    zeros) and live boxed in `fallback` — this preserves order for the
    dual-mode merge (reference: ResolveTask.cc merge-in-order) with no index
    bookkeeping.
  * device staging pads N up to a bucket (and W per str col) so the jit cache
    stays small (reference analog: one LLVM module per stage; here one XLA
    executable per (stage, schema, bucket)).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Iterable, Optional, Sequence

import numpy as np

from ..core import typesys as T
from ..core.row import Row
from . import tracing as TR


# ---------------------------------------------------------------------------
# schema flattening
# ---------------------------------------------------------------------------

LEAF_NUMERIC = {T.BOOL: np.bool_, T.I64: np.int64, T.F64: np.float64}


def flatten_type(t: T.Type, path: str = "") -> list[tuple[str, T.Type]]:
    """Leaf (path, type) pairs for a column type. Option wraps leaves.

    Leaf paths are INDEX-based ("2", "2.0", ...) — column names are metadata
    only, so duplicate or hostile names can't collide storage keys.

    An Option[Tuple[...]] column gets an extra "<path>#opt" BOOL leaf holding
    whole-tuple validity (None vs a tuple of values), in addition to its
    element leaves which become Option-wrapped.

    Types without a fixed columnar layout (List/Dict/PYOBJECT) return a single
    pyobject leaf — columns of that type are host-boxed and force rows through
    the interpreter path when touched on device.
    """
    base = t.without_option() if t.is_optional() else t
    opt = t.is_optional()

    if isinstance(base, T.TupleType):
        out: list[tuple[str, T.Type]] = []
        if opt:
            out.append((f"{path}#opt", T.BOOL))
        for i, e in enumerate(base.elements):
            sub = f"{path}.{i}" if path else str(i)
            out.extend(flatten_type(T.option(e) if opt else e, sub))
        return out
    if base in (T.BOOL, T.I64, T.F64, T.STR, T.NULL, T.EMPTYTUPLE):
        return [(path, t)]
    return [(path, T.PYOBJECT)]


def columnar_supported(t: T.Type) -> bool:
    return all(lt is not T.PYOBJECT for _, lt in flatten_type(t))


def user_columns(schema: T.RowType):
    """Auto-generated names are '_0', '_1', ... — a schema made only of them
    is an UNNAMED row (no dict access, UDFs get bare values/tuples)."""
    cols = schema.columns
    if cols and all(c == f"_{i}" for i, c in enumerate(cols)):
        return None
    return cols if cols else None


# ---------------------------------------------------------------------------
# leaf column containers (host, numpy)
# ---------------------------------------------------------------------------

@dataclass
class NumericLeaf:
    data: np.ndarray                      # [N] bool_/int64/float64
    valid: Optional[np.ndarray] = None    # [N] bool_ when Option

    def __len__(self):
        return len(self.data)


@dataclass
class StrLeaf:
    bytes: np.ndarray                     # [N, W] uint8, zero padded
    lengths: np.ndarray                   # [N] int32
    valid: Optional[np.ndarray] = None

    def __len__(self):
        return len(self.lengths)

    @property
    def width(self) -> int:
        return self.bytes.shape[1] if self.bytes.ndim == 2 else 0

    def to_wire(self) -> tuple[np.ndarray, np.ndarray]:
        """Varlen wire view: (contiguous payload of the ACTUAL row bytes,
        int32 lengths). The inverse of from_wire; the transfer analog of
        the reference serializer's offsets+payload layout
        (Serializer.h:104-138) — offsets are implied by cumsum(lengths)."""
        return matrix_to_varlen(self.bytes, self.lengths)

    @classmethod
    def from_wire(cls, payload: np.ndarray, lengths: np.ndarray, width: int,
                  valid: Optional[np.ndarray] = None) -> "StrLeaf":
        lengths = np.asarray(lengths, dtype=np.int32)
        offs = np.concatenate(
            [[0], np.cumsum(np.clip(lengths, 0, width),
                            dtype=np.int64)])[:-1]
        return cls(varlen_to_matrix(payload, offs, lengths, width),
                   lengths, valid)


@dataclass
class NullLeaf:
    """All-None column: carries only the row count."""
    n: int

    def __len__(self):
        return self.n


@dataclass
class ObjectLeaf:
    """Host-boxed python objects (List/Dict/PYOBJECT leaves)."""
    values: list

    def __len__(self):
        return len(self.values)


Leaf = NumericLeaf | StrLeaf | NullLeaf | ObjectLeaf


def encode_str_leaf(values: Sequence[Optional[str]], optional: bool) -> StrLeaf:
    n = len(values)
    encoded = [v.encode("utf-8") if v is not None else b"" for v in values]
    w = max((len(b) for b in encoded), default=0)
    w = max(w, 1)
    mat = np.zeros((n, w), dtype=np.uint8)
    lens = np.zeros(n, dtype=np.int32)
    for i, b in enumerate(encoded):
        if b:
            mat[i, : len(b)] = np.frombuffer(b, dtype=np.uint8)
        lens[i] = len(b)
    valid = None
    if optional:
        valid = np.array([v is not None for v in values], dtype=np.bool_)
    return StrLeaf(mat, lens, valid)


def decode_str(leaf: StrLeaf, i: int) -> Optional[str]:
    if leaf.valid is not None and not bool(leaf.valid[i]):
        return None
    ln = int(leaf.lengths[i])
    return bytes(leaf.bytes[i, :ln]).decode("utf-8", errors="replace")


def encode_leaf(values: Sequence[Any], t: T.Type) -> Leaf:
    base = t.without_option() if t.is_optional() else t
    opt = t.is_optional()
    n = len(values)
    if base is T.EMPTYTUPLE and opt:
        # unit value with validity: only the valid bitmap carries information
        valid = np.array([v is not None for v in values], dtype=np.bool_)
        return NumericLeaf(np.zeros(n, dtype=np.bool_), valid)
    if base is T.NULL or base is T.EMPTYTUPLE:
        return NullLeaf(n)
    if base is T.STR:
        return encode_str_leaf(values, opt)
    if base in LEAF_NUMERIC:
        dtype = LEAF_NUMERIC[base]
        if opt:
            data = np.zeros(n, dtype=dtype)
            valid = np.zeros(n, dtype=np.bool_)
            for i, v in enumerate(values):
                if v is not None:
                    data[i] = v
                    valid[i] = True
            return NumericLeaf(data, valid)
        return NumericLeaf(np.asarray(values, dtype=dtype))
    return ObjectLeaf(list(values))


def decode_leaf(leaf: Leaf, i: int) -> Any:
    if isinstance(leaf, NullLeaf):
        return None
    if isinstance(leaf, ObjectLeaf):
        return leaf.values[i]
    if isinstance(leaf, StrLeaf):
        return decode_str(leaf, i)
    if leaf.valid is not None and not bool(leaf.valid[i]):
        return None
    v = leaf.data[i]
    if leaf.data.dtype == np.bool_:
        return bool(v)
    if np.issubdtype(leaf.data.dtype, np.integer):
        return int(v)
    return float(v)


# ---------------------------------------------------------------------------
# varlen wire view (offsets + contiguous payload — the reference
# serializer's disk layout applied to the transfer wire)
# ---------------------------------------------------------------------------

def matrix_to_varlen(mat: np.ndarray,
                     lens: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """[N, W] zero-padded byte matrix -> (payload of the actual row bytes
    concatenated, int32 lengths clamped to [0, W]). Row-major boolean
    selection keeps each row's prefix contiguous and in row order, so
    offsets are exactly the exclusive cumsum of the clamped lengths."""
    n = mat.shape[0]
    w = mat.shape[1] if mat.ndim == 2 else 0
    ln = np.clip(np.asarray(lens[:n], dtype=np.int32), 0, w)
    if n == 0 or w == 0:
        return np.zeros(0, np.uint8), ln
    keep = np.arange(w, dtype=np.int32)[None, :] < ln[:, None]
    return np.ascontiguousarray(mat[:n])[keep], ln


# `varlen_to_matrix` is the fallback's path since PR 34: the packed wire's
# unpack is one native call a partition by default
# (`runtime/packing._varlen_matrices` -> `unpack_varlen` of
# native/src/fasttransfer.cpp), and this gather serves where the module is
# not loaded (`TUPLEX_TPU_NO_NATIVE`, no compiler) and `StrLeaf.from_wire`.
# Rows a block of it: the index block of the widest string
# column stays under 2 MB, which the allocator hands back from its heap;
# whole-column temporaries (12 bytes an element, 60 MB for one 48-byte column
# of a 114,688-row batch) were mapped fresh and faulted in page by page on
# every call, or not, as the heap's state had it: jobs of one process ran
# 0.6 s apart from the next one's (PERF.md section 6, PR 33)
_VARLEN_BLOCK_ROWS = 4096


def varlen_to_matrix(payload: np.ndarray, offs: np.ndarray,
                     lens: np.ndarray, w: int) -> np.ndarray:
    """(payload, per-row offsets, lengths) -> [N, w] zero-padded byte
    matrix: a gather straight into the result, a block of rows at a time
    (the technique of arrow_string_to_leaf)."""
    n = len(lens)
    mat = np.zeros((n, max(w, 1)), np.uint8)
    if n == 0 or w <= 0 or len(payload) == 0:
        return mat
    ln = np.clip(np.asarray(lens, dtype=np.int64), 0, w)
    offs = np.asarray(offs, dtype=np.int64)
    payload = np.asarray(payload, dtype=np.uint8)
    cols = np.arange(w, dtype=np.int64)[None, :]
    for a in range(0, n, _VARLEN_BLOCK_ROWS):
        out = mat[a:a + _VARLEN_BLOCK_ROWS]
        rows = slice(a, a + len(out))
        np.take(payload, offs[rows, None] + cols, mode="clip", out=out)
        out[cols >= ln[rows, None]] = 0
    return mat


# ---------------------------------------------------------------------------
# lazy (device-backed) leaves — the host side of the stage handoff
# ---------------------------------------------------------------------------

# process-wide handoff observability (tests + bench): how many lazy leaf
# dicts were created and how many leaves were forced to host (each force is
# a `d2h:lazy-load` span, which names the leaf). Reset freely.
HANDOFF_STATS = {"lazy_parts": 0, "forced": 0}


def leaf_nbytes(leaf) -> int:
    """Host bytes of one leaf's arrays (0 for a null or boxed leaf)."""
    arrays = (getattr(leaf, "data", None), getattr(leaf, "bytes", None),
              getattr(leaf, "lengths", None), getattr(leaf, "valid", None))
    return sum(int(a.nbytes) for a in arrays if isinstance(a, np.ndarray))


class LazyLeaves(dict):
    """Leaf dict whose values materialize from device arrays on first
    access. Key-set operations (iteration, membership, len) never transfer;
    value access fetches ONLY the touched leaf — a join probing one key
    column pulls that column's bytes and nothing else. items()/values()
    force everything (spill, row-wise fallbacks).

    This is what lets an intermediate partition skip the D2H round-trip
    entirely: the host dict stays empty unless some slow path actually
    needs host bytes, while the device arrays feed the next stage."""

    def __init__(self, keys, loader, tag: str = ""):
        super().__init__()
        self._keys = tuple(keys)
        self._loader = loader            # loader(path) -> Leaf
        self._tag = tag
        HANDOFF_STATS["lazy_parts"] += 1

    # -- key-set views (no transfer) ------------------------------------
    def __iter__(self):
        return iter(self._keys)

    def keys(self):
        return tuple(self._keys)

    def __len__(self):
        return len(self._keys)

    def __contains__(self, k):
        return k in self._keys

    def __bool__(self):
        return bool(self._keys)

    # -- value access (forces the touched leaf) -------------------------
    def _load(self, k):
        if not super().__contains__(k):
            HANDOFF_STATS["forced"] += 1
            # a D2H wherever a consumer forces the leaf (the loader notes
            # its bytes under `d2h_bytes:lazy_load`)
            with TR.span("d2h:lazy-load", "xfer") as sp:
                leaf = self._loader(k)
                if sp is not TR.NOOP:
                    sp.set("tag", self._tag).set("leaf", k) \
                      .set("bytes", leaf_nbytes(leaf))
            super().__setitem__(k, leaf)
            if all(dict.__contains__(self, k2) for k2 in self._keys):
                self._loader = None   # release the device-array closure
        return super().__getitem__(k)

    def __getitem__(self, k):
        if k not in self._keys:
            raise KeyError(k)
        return self._load(k)

    def get(self, k, default=None):
        if k not in self._keys:
            return default
        return self._load(k)

    def items(self):
        return [(k, self._load(k)) for k in self._keys]

    def values(self):
        return [self._load(k) for k in self._keys]

    def materialized(self) -> bool:
        return all(dict.__contains__(self, k) for k in self._keys)

    # -- inherited-dict traps: keep copies/compares consistent ----------
    # (CPython bypasses overridden accessors for some C-level dict uses;
    # force first so a partially-materialized mapping never leaks out)
    def copy(self):
        return dict(self.items())

    def __eq__(self, other):
        if isinstance(other, dict):
            return dict(self.items()) == other
        return NotImplemented

    def __ne__(self, other):
        eq = self.__eq__(other)
        return NotImplemented if eq is NotImplemented else not eq

    __hash__ = None

    def __setitem__(self, k, v):
        if k not in self._keys:
            self._keys = self._keys + (k,)
        super().__setitem__(k, v)


def decode_key_tuples(part: "Partition", indices, kidx) -> list[tuple]:
    """Key-column values for the given NORMAL rows, touching only the key
    columns' leaves (a full decode_rows would force every lazy leaf of a
    device-resident partition to host — exactly the round-trip the handoff
    exists to avoid)."""
    out = []
    for i in indices:
        i = int(i)
        out.append(tuple(part._decode_col(str(j), part.schema.types[j], i)
                         for j in kidx))
    return out


# ---------------------------------------------------------------------------
# partition
# ---------------------------------------------------------------------------

def _leaf_paths_for_value(path: str, t: T.Type, v: Any) -> Iterable[tuple[str, Any]]:
    base = t.without_option() if t.is_optional() else t
    opt = t.is_optional()
    if isinstance(base, T.TupleType):
        if opt:
            yield (f"{path}#opt", v is not None)
        for i, e in enumerate(base.elements):
            sub = f"{path}.{i}" if path else str(i)
            et = T.option(e) if opt else e
            yield from _leaf_paths_for_value(sub, et, None if v is None else v[i])
    else:
        yield (path, v)


@dataclass
class Partition:
    """A horizontal slice of a dataset in normal-case columnar layout.

    `schema` is the normal-case RowType. `leaves` maps "<col>" or
    "<col>.<i>..." paths to leaf arrays of length == num_rows. Non-conforming
    row positions are False in `normal_mask` and boxed in `fallback`
    (original python value, pre-conversion).
    """

    schema: T.RowType
    num_rows: int
    leaves: dict[str, Leaf] = field(default_factory=dict)
    normal_mask: Optional[np.ndarray] = None      # [N] bool; None => all normal
    fallback: dict[int, Any] = field(default_factory=dict)
    start_index: int = 0                          # global row offset of row 0

    @property
    def columns(self) -> tuple[str, ...]:
        return self.schema.columns

    @property
    def user_columns(self):
        """Column names as the user sees them: None when auto-generated."""
        return user_columns(self.schema)

    def n_normal(self) -> int:
        if self.normal_mask is None:
            return self.num_rows
        return int(self.normal_mask.sum())

    # -- row access (host) --------------------------------------------------
    def decode_row(self, i: int) -> Row:
        """Reconstruct the boxed row at local position i (interpreter path
        input). Fallback rows return their original boxed value."""
        cols = self.user_columns
        if i in self.fallback:
            return Row.from_value(self.fallback[i], cols)
        vals = []
        for ci, ct in enumerate(self.schema.types):
            vals.append(self._decode_col(str(ci), ct, i))
        return Row(vals, cols)

    def _decode_col(self, path: str, t: T.Type, i: int) -> Any:
        base = t.without_option() if t.is_optional() else t
        opt = t.is_optional()
        if isinstance(base, T.TupleType):
            if opt:
                ol = self.leaves[f"{path}#opt"]
                assert isinstance(ol, NumericLeaf)  # BOOL leaf: validity in data
                if not bool(ol.data[i]):
                    return None
            return tuple(
                self._decode_col(f"{path}.{j}", T.option(e) if opt else e, i)
                for j, e in enumerate(base.elements)
            )
        if base is T.EMPTYTUPLE:
            if opt:
                leaf = self.leaves[path]
                assert isinstance(leaf, NumericLeaf) and leaf.valid is not None
                return () if bool(leaf.valid[i]) else None
            return ()
        return decode_leaf(self.leaves[path], i)

    def iter_rows(self) -> Iterable[Row]:
        for i in range(self.num_rows):
            yield self.decode_row(i)

    def nbytes(self) -> int:
        lv = self.leaves
        if isinstance(lv, LazyLeaves) and not lv.materialized():
            # unforced device-backed leaves hold no host bytes; the size
            # estimate must not itself trigger the D2H it is sizing
            return int(getattr(lv, "nbytes_hint", 0))
        total = 0
        for leaf in lv.values():
            if isinstance(leaf, NumericLeaf):
                total += leaf.data.nbytes + (leaf.valid.nbytes if leaf.valid is not None else 0)
            elif isinstance(leaf, StrLeaf):
                total += leaf.bytes.nbytes + leaf.lengths.nbytes
        return total


class PartitionStream:
    """Partitions whose shapes were decided before one of them was built.

    `template` is the zero-row partition at the planned schema and leaf
    widths, `rows` the row count of every partition to come, in order:
    what a compile needs to know of a dispatch batch, with no batch built
    (`compiler/stagefn.partition_avals(template, mode, rows=m)`). Iterating
    builds the partitions, one a pull, once."""

    def __init__(self, template: Partition, rows: Sequence[int],
                 parts: Iterable[Partition]):
        self.template = template
        self.rows = list(rows)
        self._parts = parts

    def __iter__(self):
        return iter(self._parts)


def build_partition(
    values: Sequence[Any],
    schema: T.RowType,
    start_index: int = 0,
) -> Partition:
    """Encode boxed python row values into a Partition against `schema`.

    Rows that don't conform to the normal-case schema keep their position as
    placeholder slots and are boxed into `fallback` (reference: fallback
    partitions of pickled objects, PythonContext.cc:617 parallelizeAnyType).
    """
    fast = _fast_partition(values, schema, start_index)
    if fast is not None:
        return fast
    n = len(values)
    # row value shape: single column -> bare value; multi -> tuple
    multi = len(schema.columns) > 1

    normal_mask = np.ones(n, dtype=np.bool_)
    fallback: dict[int, Any] = {}
    # per-leaf collected python values (placeholder None/0 for bad rows);
    # leaf paths are column-index based so duplicate names can't collide
    leaf_types: list[tuple[str, T.Type]] = []
    for ci, ct in enumerate(schema.types):
        leaf_types.extend(flatten_type(ct, str(ci)))
    leaf_values: dict[str, list] = {p: [] for p, _ in leaf_types}
    leaf_type_map = dict(leaf_types)

    placeholders = {p: _placeholder(lt) for p, lt in leaf_types}

    def conforms(row_tuple) -> bool:
        if not (isinstance(row_tuple, tuple) and
                len(row_tuple) == len(schema.columns)):
            return False
        return all(T.python_value_conforms(rv, ct)
                   for rv, ct in zip(row_tuple, schema.types))

    for i, v in enumerate(values):
        row_tuple = v if multi else (v,)
        ok = conforms(row_tuple)
        if not ok and not multi and isinstance(v, tuple) and len(v) == 1:
            # single-column rows may arrive as 1-tuples (Row semantics)
            row_tuple = v
            ok = conforms(row_tuple)
        if not ok:
            normal_mask[i] = False
            fallback[i] = v
            for p in leaf_values:
                leaf_values[p].append(placeholders[p])
            continue
        for ci, (ct, rv) in enumerate(zip(schema.types, row_tuple)):
            for p, lv in _leaf_paths_for_value(str(ci), ct, rv):
                leaf_values[p].append(lv)

    leaves = {p: encode_leaf(vals, leaf_type_map[p]) for p, vals in leaf_values.items()}
    mask = None if len(fallback) == 0 else normal_mask
    return Partition(schema=schema, num_rows=n, leaves=leaves,
                     normal_mask=mask, fallback=fallback, start_index=start_index)


def _placeholder(t: T.Type) -> Any:
    base = t.without_option() if t.is_optional() else t
    if t.is_optional() or base is T.NULL or base is T.EMPTYTUPLE:
        return None
    if base is T.STR:
        return ""
    if base is T.BOOL:
        return False
    if base is T.I64:
        return 0
    if base is T.F64:
        return 0.0
    return None


# ---------------------------------------------------------------------------
# device staging
# ---------------------------------------------------------------------------

def bucket_size(n: int, mode: str = "q8", minimum: int = 8) -> int:
    """Padded size for a real size `n`.

    "pow2"  — next power of two. Up to ~50% padding waste (round 2 measured
              31% wasted kernel time on the 100k-row bench batch padded to
              131072), at most 1 jit shape variant per octave.
    "q8"    — quantize to 1/8 of the pow2 FLOOR: waste <= 12.5% (typically
              ~6%), at most 8 shape variants per octave. The persistent
              compile cache makes the extra variants a one-time cost; this
              is the default.
    "exact" — no padding (one executable per distinct partition size; only
              sensible for single-batch jobs or tests).
    """
    if mode == "exact" or n <= 0:
        return max(n, 1)
    n = max(n, minimum)
    p2 = 1 << (n - 1).bit_length()          # pow2 ceil
    # "fixed" was a documented alias for pow2 behavior; unknown modes also
    # degrade to pow2 (the conservative shape policy) rather than silently
    # changing padding semantics
    if mode != "q8" or n == p2:
        return p2
    q = max(minimum, (p2 >> 1) >> 3)        # pow2floor / 8
    return ((n + q - 1) // q) * q


def general_batch_size(k: int, part_rows: int, mode: str = "q8") -> int:
    """Padded rows of the general tier's batch for `k` deviant rows of a
    partition of `part_rows` rows: a function of the partition's OWN staged
    bucket, never of how many rows deviated. The floor is 1/32 of that
    bucket (3,584 rows for a 111,111-row Zillow partition, of which
    1,402-1,572 reach the tier, 1.3-1.4%: a file twice as dirty still
    meets the floor) and the size doubles from there up to the bucket
    itself, so one partition size meets at most six shapes and a file of
    the same distribution the floor alone. Sized by `bucket_size(k)`
    instead, those rows landed on one to three q8 steps by the seed, each
    another executable to load or, in a new file's first job, to compile
    (PERF.md section 6, PR 29)."""
    if mode == "exact" or k <= 0:
        return max(k, 1)
    b = bucket_size(part_rows, mode)
    rows = max(8, b >> 5)
    while rows < k:
        rows <<= 1
    return min(rows, b)


def pad_to(arr: np.ndarray, n: int, axis: int = 0) -> np.ndarray:
    cur = arr.shape[axis]
    if cur >= n:
        return arr
    pad_width = [(0, 0)] * arr.ndim
    pad_width[axis] = (0, n - cur)
    return np.pad(arr, pad_width)


@dataclass
class DeviceBatch:
    """The jit-facing view of a partition: dict of padded numpy/jnp arrays.

    arrays keys: for each leaf path P:
        P            -> numeric data     [B]
        P#bytes      -> str bytes        [B, Wb]
        P#len        -> str lengths      [B]
        P#valid      -> validity         [B]      (Option leaves only)
    plus:
        "#rowvalid"  -> [B] bool — True for real, normal-case rows
    `n` is the real row count, `b` the padded bucket size.
    """

    arrays: dict[str, np.ndarray]
    n: int
    b: int
    schema: T.RowType

    def spec(self) -> tuple:
        """Hashable shape/dtype signature — the jit cache key component."""
        return tuple(sorted(
            (k, v.shape, str(v.dtype)) for k, v in self.arrays.items()
        ))


def host_nbytes(arrays: dict) -> int:
    """Bytes of a staged batch that still live on the host: what a
    per-leaf dispatch uploads (a leaf already on the device costs none)."""
    return sum(v.nbytes for v in arrays.values() if isinstance(v, np.ndarray))


def _leaf_keys(path: str, leaf):
    """Device array keys for one leaf — THE single definition of the
    per-leaf key layout (staged_keys and stage_partition both derive from
    it). [] for layout-free leaves (Null), None for host-only (Object)."""
    if isinstance(leaf, NullLeaf):
        return []
    if isinstance(leaf, ObjectLeaf):
        return None
    keys = [path] if isinstance(leaf, NumericLeaf) \
        else [path + "#bytes", path + "#len"]
    if leaf.valid is not None:
        keys.append(path + "#valid")
    return keys


def staged_keys(part: Partition):
    """The array keys stage_partition would produce for `part` (without
    '#rowvalid'/'#seed'), or None when a leaf has no device layout."""
    keys: set = set()
    for path, leaf in part.leaves.items():
        ks = _leaf_keys(path, leaf)
        if ks is None:
            return None
        keys.update(ks)
    return keys


def staged_keys_for_type(path: str, lt: T.Type) -> list[str]:
    """Device-array keys stage_partition would produce for a leaf of
    type `lt` at `path` — the TYPE-level twin of _leaf_keys, for layouts
    that exist only as device arrays (no Leaf instance to inspect).
    Kept next to _leaf_keys so the two definitions evolve together."""
    base = lt.without_option() if lt.is_optional() else lt
    opt = lt.is_optional()
    if path.endswith("#opt"):
        return [path]                       # BOOL validity leaf
    if base is T.NULL:
        return []
    if base is T.EMPTYTUPLE:
        return [path, path + "#valid"] if opt else []
    ks = [path + "#bytes", path + "#len"] if base is T.STR else [path]
    if opt:
        ks.append(path + "#valid")
    return ks


def partition_seed(part: Partition):
    """Per-partition PRNG seed (Weyl-mixed start index) for compiled
    `random` UDFs — distinct per partition so batches don't replay one
    sequence."""
    return np.uint32((part.start_index * 2654435761 + 97531) & 0xFFFFFFFF)


def stage_partition(part: Partition, bucket_mode: str = "q8",
                    force_b: Optional[int] = None,
                    force_widths: Optional[dict] = None) -> DeviceBatch:
    """`force_b` / `force_widths` override the data-derived bucket sizes —
    multi-process host-block staging must agree on GLOBAL shapes across
    hosts whose local data differs (parallel/hostio)."""
    dv = getattr(part, "device_batch", None)
    if dv is not None:
        # one-shot: drop the partition's reference either way so device
        # memory is released as soon as the consumer's dispatch retires
        # (host leaves stay authoritative for any retry)
        part.device_batch = None
        if force_b is None and force_widths is None \
                and dv.n == part.num_rows \
                and dv.b == bucket_size(part.num_rows, bucket_mode):
            return dv   # device-resident view from the producing stage
    n = part.num_rows
    b = force_b if force_b is not None else bucket_size(n, bucket_mode)
    arrays: dict[str, np.ndarray] = {}
    for path, leaf in part.leaves.items():
        ks = _leaf_keys(path, leaf)
        if not ks:   # NullLeaf (layout-free) or host-only ObjectLeaf:
            continue  # device code must not touch it
        if isinstance(leaf, NumericLeaf):
            arrays[path] = pad_to(leaf.data, b)
        else:   # StrLeaf
            wb = None if force_widths is None else force_widths.get(path)
            if wb is None:
                wb = bucket_size(max(leaf.width, 1), bucket_mode, minimum=8)
            arrays[path + "#bytes"] = pad_to(pad_to(leaf.bytes, b, 0), wb, 1)
            arrays[path + "#len"] = pad_to(leaf.lengths, b)
        if path + "#valid" in ks:
            arrays[path + "#valid"] = pad_to(leaf.valid, b)
    rowvalid = np.zeros(b, dtype=np.bool_)
    if part.normal_mask is None:
        rowvalid[:n] = True
    else:
        rowvalid[:n] = part.normal_mask
    arrays["#rowvalid"] = rowvalid
    # per-partition PRNG seed for compiled `random` UDFs (Weyl-mixed start
    # index so partitions draw distinct streams). Stages without random never
    # read it; jit drops unused inputs at lowering, so the executable and the
    # persistent compile cache key are untouched for such stages.
    arrays["#seed"] = partition_seed(part)
    return DeviceBatch(arrays=arrays, n=n, b=b, schema=part.schema)


# ---------------------------------------------------------------------------
# rebuild partitions from device outputs
# ---------------------------------------------------------------------------

def schema_for_result_type(t: "T.Type", columns: Optional[Sequence[str]] = None) -> T.RowType:
    """Row schema for a UDF/stage result type: a plain tuple spreads into
    columns, everything else is a single column. Auto column names start with
    '_' (the unnamed-row convention)."""
    if isinstance(t, T.TupleType) and not t.is_optional():
        names = tuple(columns) if columns and len(columns) == len(t.elements) \
            else tuple(f"_{i}" for i in range(len(t.elements)))
        return T.row_of(names, t.elements)
    name = tuple(columns) if columns and len(columns) == 1 else ("_0",)
    return T.row_of(name, (t,))


def partition_from_arrays(
    arrays: dict[str, np.ndarray],
    schema: T.RowType,
    n: int,
    normal_mask: Optional[np.ndarray] = None,
    fallback: Optional[dict[int, Any]] = None,
    start_index: int = 0,
) -> Partition:
    """Inverse of stage_partition: trim padded output arrays to n rows and
    wrap them as a Partition (leaf-path convention of flatten_type)."""
    leaves: dict[str, Leaf] = {}
    for ci, ct in enumerate(schema.types):
        for path, lt in flatten_type(ct, str(ci)):
            base = lt.without_option() if lt.is_optional() else lt
            opt = lt.is_optional()
            valid = arrays.get(path + "#valid")
            valid = None if valid is None else np.asarray(valid[:n], dtype=np.bool_)
            if path.endswith("#opt"):
                leaves[path] = NumericLeaf(np.asarray(arrays[path][:n], dtype=np.bool_))
                continue
            if base is T.STR:
                leaves[path] = StrLeaf(
                    np.asarray(arrays[path + "#bytes"][:n], dtype=np.uint8),
                    np.asarray(arrays[path + "#len"][:n], dtype=np.int32),
                    valid,
                )
            elif base is T.NULL:
                leaves[path] = NullLeaf(n)
            elif base is T.EMPTYTUPLE:
                if opt:
                    leaves[path] = NumericLeaf(np.zeros(n, dtype=np.bool_), valid)
                else:
                    leaves[path] = NullLeaf(n)
            elif base in LEAF_NUMERIC:
                leaves[path] = NumericLeaf(
                    np.asarray(arrays[path][:n], dtype=LEAF_NUMERIC[base]), valid)
            else:
                raise ValueError(f"cannot rebuild leaf {path}: {lt}")
    return Partition(schema=schema, num_rows=n, leaves=leaves,
                     normal_mask=normal_mask, fallback=dict(fallback or {}),
                     start_index=start_index)


def type_from_result_arrays(arrays: dict, path: str) -> Optional[T.Type]:
    """Reconstruct a leaf/column type from device-output array keys: the key
    suffix convention + dtypes fully determine the type, so the rebuilt
    partition always matches what the trace ACTUALLY produced (never the
    sample-speculated schema)."""
    # fast existence probe: nothing under this path => no such column
    if not any(k == path or k.startswith(path + "#") or
               k.startswith(path + ".") for k in arrays):
        return None
    opt = (path + "#valid") in arrays or (path + "#opt") in arrays
    if (path + "#bytes") in arrays:
        return T.option(T.STR) if opt else T.STR
    if (path + "#null") in arrays:
        return T.NULL
    if (path + "#unit") in arrays:
        return T.option(T.EMPTYTUPLE) if opt else T.EMPTYTUPLE
    if path in arrays:
        # dtype attribute, not np.asarray: schema probing must work on
        # DEVICE arrays without pulling their bytes to host (lazy handoff)
        dt = np.dtype(getattr(arrays[path], "dtype", None) or
                      np.asarray(arrays[path]).dtype)
        if dt == np.bool_:
            base = T.BOOL
        elif np.issubdtype(dt, np.integer):
            base = T.I64
        else:
            base = T.F64
        return T.option(base) if opt else base
    # tuple: children at path.0, path.1, ...
    elts = []
    i = 0
    while True:
        sub = f"{path}.{i}" if path else str(i)
        et = type_from_result_arrays(arrays, sub)
        if et is None:
            break
        elts.append(et)
        i += 1
    if not elts:
        return None
    tt = T.tuple_of(*[e.without_option() if opt and e.is_optional() else e
                      for e in elts]) if opt else T.tuple_of(*elts)
    return T.option(tt) if opt else tt


def partition_from_result_arrays(
    arrays: dict[str, np.ndarray],
    n: int,
    columns: Optional[Sequence[str]] = None,
    start_index: int = 0,
) -> Partition:
    """Build a Partition directly from stage-output arrays (cv_output_arrays
    key convention), deriving the schema from the arrays themselves."""
    col_types = []
    ci = 0
    while True:
        t = type_from_result_arrays(arrays, str(ci))
        if t is None:
            break
        col_types.append(t)
        ci += 1
    if not col_types:
        raise ValueError("no columns found in result arrays")
    names = tuple(columns) if columns and len(columns) == len(col_types) \
        else tuple(f"_{i}" for i in range(len(col_types)))
    schema = T.row_of(names, col_types)

    leaves: dict[str, Leaf] = {}
    for ci, ct in enumerate(col_types):
        for path, lt in flatten_type(ct, str(ci)):
            leaves[path] = leaf_from_result_arrays(arrays, path, lt, n)
    return Partition(schema=schema, num_rows=n, leaves=leaves,
                     start_index=start_index)


def result_keys_for_leaf(arrays: dict, path: str) -> list[str]:
    """The result-array keys leaf_from_result_arrays reads for `path` —
    the unit of a lazy per-leaf fetch."""
    ks = [k for k in (path, path + "#bytes", path + "#len",
                      path + "#valid", path + "#opt") if k in arrays]
    return ks


def leaf_from_result_arrays(arrays: dict, path: str, lt: T.Type,
                            n: int) -> Leaf:
    """One leaf of a result partition from stage-output arrays (the
    per-path unit of partition_from_result_arrays; lazy handoff loaders
    call it with just that leaf's fetched arrays)."""
    base = lt.without_option() if lt.is_optional() else lt
    opt = lt.is_optional()
    if path.endswith("#opt"):
        return NumericLeaf(np.asarray(arrays[path][:n], dtype=np.bool_))
    valid = arrays.get(path + "#valid")
    if valid is None and opt and (path + "#opt") in arrays:
        valid = arrays[path + "#opt"]
    valid = None if valid is None else np.asarray(valid[:n], dtype=np.bool_)
    if base is T.STR:
        return StrLeaf(
            np.asarray(arrays[path + "#bytes"][:n], dtype=np.uint8),
            np.asarray(arrays[path + "#len"][:n], dtype=np.int32),
            valid)
    if base is T.NULL:
        return NullLeaf(n)
    if base is T.EMPTYTUPLE:
        if opt:
            return NumericLeaf(
                np.zeros(n, dtype=np.bool_),
                valid if valid is not None else np.ones(n, dtype=np.bool_))
        return NullLeaf(n)
    return NumericLeaf(
        np.asarray(arrays[path][:n], dtype=LEAF_NUMERIC[base]), valid)


def gather_partition(part: Partition, out_positions: np.ndarray,
                     src_indices: np.ndarray, m: int) -> Partition:
    """New m-row partition with rows src_indices placed at out_positions
    (other slots zero placeholders, to be filled by resolved rows)."""
    leaves: dict[str, Leaf] = {}
    for path, leaf in part.leaves.items():
        if isinstance(leaf, NumericLeaf):
            data = np.zeros(m, dtype=leaf.data.dtype)
            valid = None if leaf.valid is None else np.zeros(m, np.bool_)
            if len(src_indices):
                data[out_positions] = leaf.data[src_indices]
                if valid is not None:
                    valid[out_positions] = leaf.valid[src_indices]
            leaves[path] = NumericLeaf(data, valid)
        elif isinstance(leaf, StrLeaf):
            b = np.zeros((m, max(leaf.width, 1)), dtype=np.uint8)
            ln = np.zeros(m, dtype=np.int32)
            valid = None if leaf.valid is None else np.zeros(m, np.bool_)
            if len(src_indices):
                b[out_positions] = leaf.bytes[src_indices]
                ln[out_positions] = leaf.lengths[src_indices]
                if valid is not None:
                    valid[out_positions] = leaf.valid[src_indices]
            leaves[path] = StrLeaf(b, ln, valid)
        elif isinstance(leaf, NullLeaf):
            leaves[path] = NullLeaf(m)
        else:
            vals: list = [None] * m
            for o, s in zip(out_positions.tolist(), src_indices.tolist()):
                vals[o] = leaf.values[s]
            leaves[path] = ObjectLeaf(vals)
    return Partition(schema=part.schema, num_rows=m, leaves=leaves,
                     start_index=part.start_index)


def unique_rows(sub: np.ndarray):
    """np.unique(view_as_void, return_index, return_inverse) semantics over
    the rows of a [N, W] uint8 matrix — (inverse int32, first_idx int64),
    groups numbered in byte-lexicographic order, first_idx = smallest
    original row index per group.

    np.unique on a void view argsorts with generic memcmp comparisons
    (~38ms for 60k x 24 on one core — half of tpch q1's aggregate cost);
    a stable lexsort over big-endian u64 lanes is typed and ~10x faster."""
    n, w = sub.shape
    if n == 0:
        return np.zeros(0, np.int32), np.zeros(0, np.int64)
    wp = -(-max(w, 1) // 8) * 8
    if wp != w:
        sub = np.pad(sub, ((0, 0), (0, wp - w)))
    # big-endian lanes: u64 numeric order == byte-lexicographic order
    cols = np.ascontiguousarray(sub).view(">u8").reshape(n, wp // 8)
    order = np.lexsort(cols.T[::-1])     # primary key = first lane
    s = cols[order]
    bound = np.empty(n, dtype=bool)
    bound[0] = True
    if n > 1:
        np.any(s[1:] != s[:-1], axis=1, out=bound[1:])
    gid_sorted = np.cumsum(bound) - 1
    inverse = np.empty(n, dtype=np.int64)
    inverse[order] = gid_sorted
    # lexsort is stable -> the boundary row of each group carries the
    # smallest original index among its equals
    first_idx = order[np.nonzero(bound)[0]]
    return inverse.astype(np.int32), first_idx.astype(np.int64)


def key_signature_matrix(part: Partition, cis: Sequence[int],
                         reject_nan: bool = True) -> Optional[np.ndarray]:
    """[N, W] canonical byte-signature matrix over the given key columns,
    None if any leaf isn't signature-comparable. Byte equality must IMPLY
    python equality, so every representation quirk is canonicalized first:
    invalid (None) slots are zeroed (CSV null_values keep their original
    sbytes; merge_cv Options carry the dead branch's data), str bytes past
    the length are zeroed (stage outputs carry stale padding), floats
    normalize -0.0 and (for joins) reject NaN since NaN != NaN."""
    pieces: list[np.ndarray] = []
    n = part.num_rows
    for ci in cis:
        for path, _lt in flatten_type(part.schema.types[ci], str(ci)):
            leaf = part.leaves.get(path)
            if isinstance(leaf, NumericLeaf):
                data = leaf.data
                if leaf.valid is not None:
                    data = np.where(
                        leaf.valid.reshape((n,) + (1,) * (data.ndim - 1)),
                        data, 0)
                if data.dtype.kind == "f":
                    if reject_nan and np.isnan(data).any():
                        return None  # NaN keys: python equality differs
                    data = np.where(data == 0, 0.0, data)  # -0.0 == 0.0
                pieces.append(np.ascontiguousarray(
                    data.reshape(n, -1)).view(np.uint8).reshape(n, -1))
                if leaf.valid is not None:
                    pieces.append(leaf.valid.reshape(-1, 1).view(np.uint8))
            elif isinstance(leaf, StrLeaf):
                b, ln = leaf.bytes, leaf.lengths
                if leaf.valid is not None:
                    b = np.where(leaf.valid[:, None], b, 0)
                    ln = np.where(leaf.valid, ln, 0)
                b = np.where(
                    np.arange(b.shape[1])[None, :] < ln[:, None], b, 0)
                pieces.append(b)
                pieces.append(ln.astype("<i4").view(np.uint8).reshape(n, -1))
                if leaf.valid is not None:
                    pieces.append(leaf.valid.reshape(-1, 1).view(np.uint8))
            elif isinstance(leaf, NullLeaf):
                pieces.append(np.zeros((n, 1), np.uint8))
            else:
                return None
    if not pieces:
        return None
    return np.ascontiguousarray(np.concatenate(pieces, axis=1))


def harmonize_partitions(parts: list) -> list:
    """Pad every partition's str leaves to the dataset-wide bucketed width
    and align row-count buckets, so ONE jit executable serves every partition
    (reference analog: one LLVM module per stage regardless of partition
    count). Without this each partition's distinct shapes would recompile."""
    if not parts:
        return parts
    widths: dict[str, int] = {}
    for p in parts:
        for path, leaf in p.leaves.items():
            if isinstance(leaf, StrLeaf):
                widths[path] = max(widths.get(path, 1), leaf.width)
    for path in widths:
        widths[path] = bucket_size(widths[path], minimum=8)
    for p in parts:
        for path, w in widths.items():
            leaf = p.leaves.get(path)
            if isinstance(leaf, StrLeaf) and leaf.width < w:
                leaf.bytes = pad_to(leaf.bytes, w, axis=1)
    return parts


def _leaf_to_pylist(leaf: Leaf, n: int) -> list:
    """Bulk-decode one leaf to python values (C-speed paths)."""
    if isinstance(leaf, NullLeaf):
        return [None] * n
    if isinstance(leaf, ObjectLeaf):
        return list(leaf.values[:n])
    if isinstance(leaf, NumericLeaf):
        vals = leaf.data[:n].tolist()
        if leaf.valid is not None:
            v = leaf.valid
            return [x if v[i] else None for i, x in enumerate(vals)]
        return vals
    # StrLeaf: one flat buffer + byte slicing beats per-row np indexing
    w = leaf.bytes.shape[1] if leaf.bytes.ndim == 2 else 1
    flat = np.ascontiguousarray(leaf.bytes[:n]).tobytes()
    from ..native import get as _native_get

    nat = _native_get()
    if nat is not None:
        lens_b = np.ascontiguousarray(
            leaf.lengths[:n].astype(np.int32)).tobytes()
        decoded = nat.decode_str(flat, lens_b, w, n)
        if leaf.valid is not None:
            vv = leaf.valid[:n].tolist()
            return [decoded[i] if vv[i] else None for i in range(n)]
        return decoded
    lens = leaf.lengths[:n].tolist()
    if leaf.valid is not None:
        vv = leaf.valid[:n].tolist()
        return [
            flat[i * w: i * w + lens[i]].decode("utf-8", "replace")
            if vv[i] else None
            for i in range(n)
        ]
    return [flat[i * w: i * w + lens[i]].decode("utf-8", "replace")
            for i in range(n)]


def decode_rows(part: Partition, indices) -> "list[Row]":
    """Bulk-decode the given local row positions into boxed Rows — the
    batched replacement for per-row decode_row on the interpreter path
    (reference analog: PythonDataSet.cc bulk converters)."""
    from ..core.row import Row

    idx = np.asarray(list(indices), dtype=np.int64)
    m = len(idx)
    if m == 0:
        return []
    cols = part.user_columns
    single = len(part.schema.types) == 1
    gp = gather_partition(part, np.arange(m, dtype=np.int64), idx, m)
    gp.fallback = {}
    vals = partition_to_pylist(gp)
    fb = part.fallback
    rows: list[Row] = []
    for j, i in enumerate(idx.tolist()):
        if i in fb:
            rows.append(Row.from_value(fb[i], cols))
        elif single:
            rows.append(Row((vals[j],), cols))
        else:
            rows.append(Row(vals[j], cols))
    return rows


def _decode_columns_native(part: Partition, n: int) -> Optional[list]:
    """One-pass C decode of a flat-primitive partition into row tuples
    (reference analog: PythonDataSet.cc:1400-1442 resultSetToCPython's
    per-type bulk decoders). None when the schema has nested/object
    columns or the native module is unavailable."""
    from ..native import get as native_get

    nat = native_get()
    if nat is None or not hasattr(nat, "decode_columns"):
        return None
    codes = {T.I64: 0, T.F64: 1, T.BOOL: 2, T.STR: 3}
    spec = []
    for ci, t in enumerate(part.schema.types):
        base = t.without_option() if t.is_optional() else t
        code = codes.get(base)
        leaf = part.leaves.get(str(ci))
        if code is None or leaf is None:
            return None
        valid = None
        if getattr(leaf, "valid", None) is not None:
            valid = np.ascontiguousarray(
                np.asarray(leaf.valid[:n]).astype(np.uint8, copy=False))
        if code == 3:
            if not isinstance(leaf, StrLeaf):
                return None
            mat = np.ascontiguousarray(np.asarray(leaf.bytes[:n]))
            w = mat.shape[1] if mat.ndim == 2 else 1
            lens = np.ascontiguousarray(
                np.asarray(leaf.lengths[:n]).astype(np.int32, copy=False))
            spec.append((3, mat, valid, lens, w))
        else:
            if not isinstance(leaf, NumericLeaf):
                return None
            data = np.asarray(leaf.data[:n])
            want = {0: np.int64, 1: np.float64, 2: np.uint8}[code]
            data = np.ascontiguousarray(data.astype(want, copy=False))
            spec.append((code, data, valid))
    return nat.decode_columns(spec, n)


def partition_to_pylist(part: Partition) -> list:
    """Bulk row decode (reference analog: PythonDataSet.cc fast decoders —
    bulk converters instead of per-row boxing)."""
    return box_rows(part)[0]


def box_rows(part: Partition) -> "tuple[list, bool]":
    """`partition_to_pylist`, and whether the native decoder built the rows
    (False: the per-column Python path, or nothing to decode)."""
    n = part.num_rows
    if n == 0:
        return [], False  # empty partitions may carry no leaf arrays at all
    single = len(part.schema.types) == 1
    out_fast = _decode_columns_native(part, n)
    if out_fast is not None:
        out = out_fast
    else:
        cols = []
        for ci, ct in enumerate(part.schema.types):
            cols.append(_column_pylist(part, str(ci), ct, n))
        if single:
            out = list(cols[0])
        else:
            out = list(zip(*cols))
    if part.fallback:
        for i, v in part.fallback.items():
            # Row.from_value semantics: single-field tuples collect bare
            if single and isinstance(v, tuple) and len(v) == 1:
                out[i] = v[0]
            else:
                out[i] = v
    return out, out_fast is not None


def _column_pylist(part: Partition, path: str, t: T.Type, n: int) -> list:
    base = t.without_option() if t.is_optional() else t
    opt = t.is_optional()
    if isinstance(base, T.TupleType):
        sub = [
            _column_pylist(part, f"{path}.{j}", T.option(e) if opt else e, n)
            for j, e in enumerate(base.elements)
        ]
        tuples = list(zip(*sub)) if sub else [()] * n
        if opt:
            ol = part.leaves[f"{path}#opt"]
            assert isinstance(ol, NumericLeaf)
            ov = ol.data[:n].tolist()
            return [tuples[i] if ov[i] else None for i in range(n)]
        return tuples
    if base is T.EMPTYTUPLE:
        if opt:
            leaf = part.leaves[path]
            assert isinstance(leaf, NumericLeaf) and leaf.valid is not None
            return [() if leaf.valid[i] else None for i in range(n)]
        return [()] * n
    return _leaf_to_pylist(part.leaves[path], n)


# ---------------------------------------------------------------------------
# native fast transfer (reference: PythonContext.cc fast paths)
# ---------------------------------------------------------------------------

def _fast_partition(values: Sequence[Any], schema: T.RowType,
                    start_index: int) -> Optional[Partition]:
    """C-kernel bulk encode for flat primitive schemas; None if the schema
    or the native module isn't eligible (generic python path then runs)."""
    from ..native import get as native_get

    nat = native_get()
    if nat is None:
        return None
    kinds = []
    for t in schema.types:
        base = t.without_option() if t.is_optional() else t
        if base is T.I64:
            kinds.append(("i64", t.is_optional()))
        elif base is T.F64:
            kinds.append(("f64", t.is_optional()))
        elif base is T.BOOL:
            kinds.append(("bool", t.is_optional()))
        elif base is T.STR:
            kinds.append(("str", t.is_optional()))
        else:
            return None
    n = len(values)
    k = len(kinds)
    multi = k > 1

    if multi and hasattr(nat, "encode_rows"):
        return _fast_partition_rows(nat, values, schema, kinds, start_index)

    # split rows into per-column python lists (C-speed zip for clean rows)
    bad_rows: set[int] = set()
    if multi:
        clean = True
        for v in values:
            if not (type(v) is tuple and len(v) == k):
                clean = False
                break
        if clean:
            cols = [list(c) for c in zip(*values)] if n else [[] for _ in kinds]
        else:
            cols = [[None] * n for _ in range(k)]
            for i, v in enumerate(values):
                if type(v) is tuple and len(v) == k:
                    for ci in range(k):
                        cols[ci][i] = v[ci]
                else:
                    bad_rows.add(i)
    else:
        cols = [[v[0] if type(v) is tuple and len(v) == 1 else v
                 for v in values]]

    leaves: dict[str, Leaf] = {}
    for ci, (kind, opt) in enumerate(kinds):
        col = cols[ci]
        if kind == "str":
            mat_b, lens_b, valid_b, w, bad = nat.encode_str(col)
            enc = (mat_b, lens_b, valid_b, w)
        else:
            encode = {"i64": nat.encode_i64, "f64": nat.encode_f64,
                      "bool": nat.encode_bool}[kind]
            data_b, valid_b, bad = encode(col)
            enc = (data_b, valid_b)
        leaves[str(ci)], valid = _leaf_from_encoded(kind, opt, enc, n)
        bad_rows.update(bad)
        if not opt:
            # None in a non-Option column deviates from the normal case
            bad_rows.update(np.nonzero(~valid)[0].tolist())
    return _partition_with_fallback(schema, n, leaves, start_index,
                                    bad_rows, values)


def _fast_partition_rows(nat, values: Sequence[Any], schema: T.RowType,
                         kinds, start_index: int) -> Partition:
    """Mixed-tuple bulk encode: ONE C pass over the row tuples builds every
    column buffer (reference analog: PythonContext.cc:860
    fastMixedSimpleTypeTupleTransfer), replacing the python-side transpose +
    per-column encoders. Non-conforming rows (arity/type/overflow) come back
    in bad_list and box into the fallback dict."""
    n = len(values)
    codes = {"i64": 0, "f64": 1, "bool": 2, "str": 3}
    cols_enc, bad = nat.encode_rows(list(values),
                                    [codes[kd] for kd, _ in kinds])
    bad_rows: set[int] = set(bad)
    leaves: dict[str, Leaf] = {}
    for ci, (kind, opt) in enumerate(kinds):
        leaves[str(ci)], valid = _leaf_from_encoded(kind, opt,
                                                    cols_enc[ci], n)
        if not opt:
            # None in a non-Option column deviates from the normal case
            bad_rows.update(np.nonzero(~valid)[0].tolist())
    return _partition_with_fallback(schema, n, leaves, start_index,
                                    bad_rows, values)


def _leaf_from_encoded(kind: str, opt: bool, enc: tuple, n: int):
    """C-encoder buffers -> Leaf + full validity array (shared by the
    per-column and mixed-tuple encode paths)."""
    if kind == "str":
        mat_b, lens_b, valid_b, w = enc
        mat = np.frombuffer(mat_b, dtype=np.uint8).reshape(n, w).copy() \
            if n else np.zeros((0, max(w, 1)), np.uint8)
        lens = np.frombuffer(lens_b, dtype=np.int32).copy()
        valid = np.frombuffer(valid_b, dtype=np.uint8).astype(np.bool_)
        return StrLeaf(mat, lens, valid.copy() if opt else None), valid
    data_b, valid_b = enc
    dtype = {"i64": np.int64, "f64": np.float64, "bool": np.uint8}[kind]
    data = np.frombuffer(data_b, dtype=dtype).copy()
    if kind == "bool":
        data = data.astype(np.bool_)
    valid = np.frombuffer(valid_b, dtype=np.uint8).astype(np.bool_)
    return NumericLeaf(data, valid.copy() if opt else None), valid


def _partition_with_fallback(schema: T.RowType, n: int, leaves: dict,
                             start_index: int, bad_rows: set,
                             values: Sequence[Any]) -> Partition:
    part = Partition(schema=schema, num_rows=n, leaves=leaves,
                     start_index=start_index)
    if bad_rows:
        mask = np.ones(n, dtype=np.bool_)
        fallback = {}
        for i in sorted(bad_rows):
            mask[i] = False
            fallback[i] = values[i]
        part.normal_mask = mask
        part.fallback = fallback
    return part


def arrow_string_to_leaf(arr, n: int, max_w: int,
                         valid: Optional[np.ndarray] = None,
                         return_full_lens: bool = False, width: int = 0):
    """Arrow large_string array -> fixed-width byte-matrix leaf (vectorized
    offsets gather; shared by the CSV and ORC sources). With
    return_full_lens, also returns the UNCLAMPED byte lengths so callers can
    detect over-long cells without re-reading the buffers. `width` > 0 is
    the matrix's width, decided before this slice was looked at (a CSV
    source's planned width: no pad pass afterwards); 0 derives it from the
    slice's widest cell. A cell is clamped to min(width, max_w)."""
    buffers = arr.buffers()
    from ..native import get as _native_get

    nat = _native_get()
    if nat is not None and hasattr(nat, "offsets_to_matrix") and n:
        mat_b, lens_b, full_b, w = nat.offsets_to_matrix(
            buffers[2] if buffers[2] else b"", buffers[1], n, arr.offset,
            max_w, width)
        mat = np.frombuffer(mat_b, dtype=np.uint8).reshape(n, w)
        leaf = StrLeaf(mat, np.frombuffer(lens_b, dtype=np.int32), valid)
        if return_full_lens:
            return leaf, np.frombuffer(full_b, dtype=np.int64)
        return leaf
    offsets = np.frombuffer(buffers[1], dtype=np.int64,
                            count=len(arr) + 1 + arr.offset)[arr.offset:]
    data = np.frombuffer(buffers[2], dtype=np.uint8) if buffers[2] \
        else np.zeros(0, np.uint8)
    starts = offsets[:-1]
    lens = (offsets[1:] - starts).astype(np.int64)
    w = width if width > 0 else \
        int(min(max(int(lens.max()) if n else 1, 1), max_w))
    cap = min(w, max(max_w, 0))
    idx = starts[:, None] + np.arange(w, dtype=np.int64)[None, :]
    np.clip(idx, 0, max(len(data) - 1, 0), out=idx)
    mat = data[idx] if len(data) else np.zeros((n, w), np.uint8)
    keep = np.arange(w, dtype=np.int64)[None, :] < \
        np.minimum(lens, cap)[:, None]
    mat = np.where(keep, mat, 0).astype(np.uint8)
    leaf = StrLeaf(mat, np.minimum(lens, cap).astype(np.int32), valid)
    return (leaf, lens) if return_full_lens else leaf
