"""CSV ingestion: sniffing, Arrow-backed bulk reads, device-fused decoding.

Re-designs the reference's CSV stack (reference:
utils/src/CSVStatistic.cc — sample-based delimiter/header/type sniffing;
core/src/logical/FileInputOperator.cc:195-260 — normal-case vs general-case
row type; physical/JITCSVSourceTaskBuilder.cc + CSVParseRowGenerator.cc —
parsing fused INTO the compiled pipeline) for the TPU model:

  * sniffing: python-side over a 256KB sample (delimiter candidates scored by
    per-line count consistency, header detected by type mismatch, per-column
    normal-case type at tuplex.normalcaseThreshold)
  * bulk read: pyarrow.csv (Arrow C++, multithreaded) with ALL columns read
    as strings — structural parsing only, no type conversion on host
  * shapes first: a whole read (`stream_partitions`) plans every column's
    dataset-wide byte width off the Arrow offsets and every partition's
    rows BEFORE one partition is built, then cuts each partition at that
    shape when the stage pulls it — on the backend's prefetch thread,
    beside the chip; no pad pass follows (one executable per stage needs
    one width set; `take(n)` streams record batches instead and pays a
    retrace where widths differ)
  * type decoding runs ON DEVICE inside the fused stage function
    (DecodeOperator → parse_i64/parse_f64 kernels + null-value matching);
    cells that fail to parse raise into the error lattice and re-run on the
    interpreter — the dual-mode CSV semantics of the reference, vectorized
"""

from __future__ import annotations

import csv as _pycsv
import io as _io
from typing import Any, Optional, Sequence

import numpy as np

from ..core import typesys as T
from ..core.errors import TuplexException
from ..core.row import Row
from ..plan import logical as L
from ..runtime import columns as C
from ..runtime import tracing as TR
from ..runtime import xferstats
from .vfs import VirtualFileSystem, files_fingerprint

DEFAULT_NULL_VALUES = ("",)
_DELIM_CANDIDATES = (",", ";", "|", "\t")


# ---------------------------------------------------------------------------
# sniffing (CSVStatistic semantics)
# ---------------------------------------------------------------------------

def sniff_delimiter(sample_text: str) -> str:
    lines = [ln for ln in sample_text.splitlines() if ln.strip()][:64]
    best, best_score = ",", -1.0
    for d in _DELIM_CANDIDATES:
        counts = []
        for ln in lines:
            try:
                row = next(_pycsv.reader([ln], delimiter=d))
                counts.append(len(row))
            except Exception:
                counts.append(1)
        if not counts:
            continue
        from collections import Counter

        mode, freq = Counter(counts).most_common(1)[0]
        if mode <= 1:
            score = 0.0
        else:
            score = freq / len(counts) * mode
        if score > best_score:
            best, best_score = d, score
    return best

def _cell_type(cell: str, null_values: Sequence[str]) -> T.Type:
    if cell in null_values:
        return T.NULL
    try:
        int(cell)
        return T.I64
    except ValueError:
        pass
    try:
        float(cell)
        return T.F64
    except ValueError:
        pass
    if cell.lower() in ("true", "false"):
        return T.BOOL
    return T.STR


def detect_header(rows: list[list[str]], null_values: Sequence[str]) -> bool:
    """First row is a header iff all its cells are non-numeric strings AND
    some body column has a different type (reference: CSVStatistic header
    heuristic)."""
    if len(rows) < 2:
        return False
    head = rows[0]
    if any(_cell_type(c, ()) is not T.STR or c == "" for c in head):
        return False
    body_types = []
    k = len(head)
    for ci in range(k):
        col = [r[ci] for r in rows[1:] if len(r) == k]
        ts = {_cell_type(c, null_values) for c in col} - {T.NULL}
        body_types.append(ts)
    # any column whose body is uniformly non-str => header
    if any(ts and T.STR not in ts for ts in body_types):
        return True
    # all-string file: header iff first row values never reappear
    flat = {c for r in rows[1:] for c in r}
    return not any(h in flat for h in head)


def infer_column_types(rows: list[list[str]], k: int,
                       null_values: Sequence[str], threshold: float,
                       ) -> tuple[list[T.Type], list[T.Type]]:
    """(normal_types, general_types) per column — the normal case speculates
    the majority type at the threshold; the general case is the supertype of
    every sampled cell (reference: FileInputOperator.cc:228-232 keeps BOTH
    row types; the general one feeds the compiled resolve path)."""
    types = []
    general_types = []
    for ci in range(k):
        cells = [r[ci] for r in rows if len(r) == k]
        vals: list[Any] = []
        for c in cells:
            ct = _cell_type(c, null_values)
            if ct is T.NULL:
                vals.append(None)
            elif ct is T.I64:
                vals.append(int(c))
            elif ct is T.F64:
                vals.append(float(c))
            elif ct is T.BOOL:
                vals.append(c.lower() == "true")
            else:
                vals.append(c)
        nc, gc, _ = T.normal_case_type(vals, threshold)
        if nc is T.UNKNOWN or nc is T.PYOBJECT:
            nc = T.STR
        if gc is T.UNKNOWN:
            gc = nc
        types.append(nc)
        general_types.append(_cell_general(gc))
    return types, general_types


def _cell_general(gc: T.Type) -> T.Type:
    """Any mix the supertype can't name as a primitive decodes as the raw
    string — the cells ARE strings, downstream UDFs parse them."""
    gb = gc.without_option() if gc.is_optional() else gc
    if gb not in (T.I64, T.F64, T.BOOL, T.STR, T.NULL):
        return T.option(T.STR) if gc.is_optional() else T.STR
    return gc


def widen_general_types(general_types: list, rows: list[list[str]], k: int,
                        null_values: Sequence[str]) -> list:
    """`general_types` widened by every kind of cell that `rows` show. The
    rows are windows from all over the file (`_evidence_rows`): the general
    case has to name what one row in a thousand holds, and whether the head
    of a file shows such a row is the toss of a coin."""
    rows = [r for r in rows if len(r) == k]
    out = []
    for ci, gc in enumerate(general_types):
        for cell in {r[ci] for r in rows}:
            gc = T.super_type(gc, _cell_type(cell, null_values))
        out.append(_cell_general(gc))
    return out


class CSVStatistic:
    """Sniffing result over a file sample."""

    def __init__(self, sample_bytes: bytes, options,
                 delimiter: Optional[str] = None,
                 header: Optional[bool] = None,
                 null_values: Optional[Sequence[str]] = None,
                 columns: Optional[Sequence[str]] = None,
                 type_hints: Optional[dict] = None,
                 quotechar: str = '"',
                 evidence: Sequence[bytes] = ()):
        text = sample_bytes.decode("utf-8", errors="replace")
        # drop a possibly-truncated last line
        if not sample_bytes.endswith(b"\n") and "\n" in text:
            text = text[: text.rfind("\n")]
        self.null_values = tuple(null_values) if null_values is not None \
            else DEFAULT_NULL_VALUES
        self.delimiter = delimiter or sniff_delimiter(text)
        self.quotechar = quotechar or '"'
        rows = list(_pycsv.reader(_io.StringIO(text),
                                  delimiter=self.delimiter,
                                  quotechar=self.quotechar))
        rows = [r for r in rows if r]
        if not rows:
            raise TuplexException("empty CSV sample")
        self.has_header = detect_header(rows, self.null_values) \
            if header is None else header
        body = rows[1:] if self.has_header else rows
        from collections import Counter

        k = Counter(len(r) for r in body).most_common(1)[0][0] if body else \
            len(rows[0])
        self.num_columns = k
        if columns:
            self.columns = list(columns)
        elif self.has_header:
            self.columns = [c if c else f"_{i}"
                            for i, c in enumerate(rows[0])]
        else:
            self.columns = [f"_{i}" for i in range(k)]
        threshold = options.get_float("tuplex.normalcaseThreshold", 0.9)
        max_rows = options.get_int("tuplex.csv.maxDetectionRows", 1000)
        self.types, self.general_types = infer_column_types(
            body[:max_rows], k, self.null_values, threshold)
        for block in evidence:
            # a window cut out of the file: its first and last lines are
            # pieces of lines
            lines = block.decode("utf-8", errors="replace") \
                .split("\n")[1:-1]
            self.general_types = widen_general_types(
                self.general_types,
                list(_pycsv.reader(lines, delimiter=self.delimiter,
                                   quotechar=self.quotechar)),
                k, self.null_values)
        if type_hints:
            for key, t in type_hints.items():
                idx = key if isinstance(key, int) else self.columns.index(key)
                self.types[idx] = t
                self.general_types[idx] = t   # a hint overrides speculation
        self.sample_rows = body[:max_rows]


# ---------------------------------------------------------------------------
# logical operators
# ---------------------------------------------------------------------------


def _host_sharded_gate(files: list, context) -> bool:
    """Common preconditions for per-host byte-range reads: real
    multi-process SPMD on the multihost backend, single-file source,
    option enabled."""
    if len(files) != 1 or not context.options_store.get_bool(
            "tuplex.tpu.hostShardedReads", True):
        return False
    from ..exec.multihost import MultiHostBackend

    if not isinstance(context.backend, MultiHostBackend):
        return False
    import jax

    nproc = jax.process_count()
    # host-block slot quantization assumes devices split evenly across
    # processes (hostblock_stage_fn pads each block to 8*ldev slots); an
    # uneven split would mis-assemble make_array_from_process_local_data,
    # so fall back to whole reads for odd topologies (3 devices / 2 hosts)
    if nproc <= 1 or context.backend.n_devices % nproc != 0:
        return False
    return True

class CSVSourceOperator(L.LogicalOperator):
    """Raw-cell CSV source: every column is Option[str] (missing cell = None).

    Typed decoding is a separate fused DecodeOperator so parsing runs on
    device (reference analog: CellSourceTaskBuilder feeding the codegen'd
    pipeline)."""

    def __init__(self, options, pattern: str, stat: CSVStatistic,
                 files: list[str]):
        super().__init__([])
        self.options = options
        self.pattern = pattern
        self.stat = stat
        self.files = files
        self._raw_schema = T.row_of(
            stat.columns, [T.option(T.STR)] * stat.num_columns)

    def schema(self) -> T.RowType:
        return self._raw_schema

    def source_key(self):
        # the stat OUTCOME (delimiter/header/columns/null values/speculated
        # types) captures every sniffing parameter incl. per-call overrides
        # and type hints — two calls that sniff identically may share
        stat = self.stat
        return files_fingerprint(
            self.files, extra=(
                self.pattern, stat.delimiter, stat.has_header,
                tuple(stat.columns), tuple(stat.null_values),
                tuple(t.name for t in stat.types),
                tuple(t.name for t in stat.general_types),
                len(stat.sample_rows)))

    def sample(self) -> list[Row]:
        k = self.stat.num_columns
        out = []
        for r in self.stat.sample_rows:
            cells: list = list(r[:k]) + [None] * max(0, k - len(r))
            out.append(Row(cells, self.stat.columns))
        return out

    # -- bulk read ----------------------------------------------------------
    def _host_sharded(self, context) -> bool:
        """Per-host byte-range CSV reads under REAL multi-process SPMD
        (reference splits CSV inputs by byte range the same way,
        inputSplitSize tasks). Newline alignment is exact only without
        quoted newlines; _load_host_sharded verifies quote-freeness over
        the WHOLE file (each host checks its own fragment, verdicts
        allgather) and falls back to whole reads otherwise."""
        return _host_sharded_gate(self.files, context)

    def load_partitions(self, context, projection=None) -> list[C.Partition]:
        if self._host_sharded(context):
            sharded = self._load_host_sharded(context, projection)
            if sharded is not None:
                return sharded
        return list(self._planned_stream(context, projection))

    def stream_partitions(self, context, projection=None):
        """The whole read as a `C.PartitionStream`: every file is read and
        the partitions' shapes are planned HERE (`ingest:read-csv`,
        `ingest:plan-shapes`); each partition is cut when the stream is
        pulled (`ingest:to-partition`), at its final width. None where this
        process reads only its byte range of the file (host-sharded): that
        block's widths are agreed across hosts, after it is built."""
        if self._host_sharded(context):
            return None
        return self._planned_stream(context, projection)

    def _planned_stream(self, context, projection=None) -> C.PartitionStream:
        stat = self.stat
        out_columns = list(projection) if projection else stat.columns
        raw_schema = T.row_of(out_columns,
                              [T.option(T.STR)] * len(out_columns))
        proj_idx = [stat.columns.index(c) for c in out_columns]
        max_w = context.options_store.get_int("tuplex.tpu.maxStrBytes", 4096)
        reads = [(path, *self._read_table(path, projection))
                 for path in self.files]
        with TR.span("ingest:plan-shapes", "io") as _sp:
            # the dataset-wide width of every column, off the Arrow offsets:
            # what harmonize_partitions finds once every partition is built
            widths = _planned_widths([t for _, t, _ in reads], max_w)
            cuts: list = []     # a file: (table, scanned, bad_rows, cap, sizes)
            rows: list[int] = []
            for path, table, bad_rows in reads:
                cap = _csv_rows_per_partition(context, table)
                scanned = None
                if bad_rows:
                    # Arrow's InvalidRow.number is None in this version, so
                    # recover each bad row's original position with one
                    # lenient python-csv scan (dirty path only) and splice it
                    # back at its slot as a boxed fallback row — keeps
                    # merge-in-order exact for malformed rows like the
                    # reference (advisor finding, round 1).
                    scanned = _scan_bad_records(path, stat)
                    if len(scanned) != len(bad_rows):
                        scanned = None
                if scanned is not None:
                    sizes = _chunk_sizes(table.num_rows + len(scanned), cap)
                    rows += sizes
                else:
                    # position recovery failed (python csv disagreed with
                    # Arrow about which rows are malformed): the bad rows
                    # trail as one partition — output order for them
                    # diverges from the reference
                    sizes = _chunk_sizes(table.num_rows, cap)
                    rows += sizes + ([len(bad_rows)] if bad_rows else [])
                cuts.append((table, scanned, bad_rows, cap, sizes))
            _sp.set("columns", len(widths)).set("partitions", len(rows)) \
               .set("widths", widths)
        template = _table_to_partition(reads[0][1].slice(0, 0), raw_schema,
                                       max_w, 0, widths)
        del reads
        return C.PartitionStream(template, rows, _cut_partitions(
            cuts, stat, raw_schema, proj_idx, max_w, widths))

    def _load_host_sharded(self, context, projection=None):
        """ONE host-block partition from this process's byte range of the
        file (parallel/hostio; executed by
        MultiHostBackend._execute_hostblock) — or None when the exact
        quote gate rejects the file (caller falls back to whole reads)."""
        import pyarrow as pa
        import pyarrow.csv as pacsv

        import jax

        from ..parallel.hostio import allgather_obj, read_bytes_range

        pid, nproc = jax.process_index(), jax.process_count()
        stat = self.stat
        frag = read_bytes_range(self.files[0], pid, nproc)
        # EXACT quote gate: the fragments cover every byte of the file, so
        # one allgathered verdict proves quote-freeness globally (a quote
        # anywhere could hide a quoted newline a byte-range split would
        # sever — potentially silently, if the severed halves still parse
        # with k cells). Quoted files re-read whole; rare and correct.
        qc = (getattr(stat, "quotechar", '"') or '"').encode()
        if any(allgather_obj(qc in frag)):
            return None
        has_header = stat.has_header and pid == 0
        bad_rows: list[tuple[int, str]] = []

        def on_invalid(row):
            bad_rows.append((row.number or 0, row.text or ""))
            return "skip"

        out_columns = list(projection) if projection else stat.columns
        raw_schema = T.row_of(out_columns,
                              [T.option(T.STR)] * len(out_columns))
        proj_idx = [stat.columns.index(c) for c in out_columns]
        max_w = context.options_store.get_int("tuplex.tpu.maxStrBytes",
                                              4096)
        if frag.strip():
            table = pacsv.read_csv(
                pa.BufferReader(frag),
                read_options=pacsv.ReadOptions(
                    use_threads=True, block_size=1 << 24,
                    column_names=stat.columns,
                    skip_rows=1 if has_header else 0,
                    autogenerate_column_names=False),
                parse_options=pacsv.ParseOptions(
                    delimiter=stat.delimiter,
                    quote_char=getattr(stat, "quotechar", '"'),
                    invalid_row_handler=on_invalid),
                convert_options=pacsv.ConvertOptions(
                    column_types={c: pa.string() for c in stat.columns},
                    include_columns=list(projection) if projection
                    else None,
                    strings_can_be_null=False))
        else:
            table = pa.table({c: pa.array([], pa.string())
                              for c in out_columns})
        if bad_rows:
            scanned = _scan_bad_records(
                self.files[0], stat,
                text=frag.decode("utf-8", errors="replace"),
                skip_header=has_header)
        else:
            scanned = []
        if bad_rows and len(scanned) == len(bad_rows):
            total = table.num_rows + len(scanned)
            part = next(_spliced_partitions(
                table, scanned, raw_schema, proj_idx, max_w,
                max(total, 1), 0))
        else:
            part = _table_to_partition(table, raw_schema, max_w, 0)
            if bad_rows:    # positions unrecoverable: trail them (rare)
                tail = _bad_rows_partition(bad_rows, stat, proj_idx,
                                           raw_schema, part.num_rows)
                vals = C.partition_to_pylist(part) +                     C.partition_to_pylist(tail)
                part = C.build_partition(vals, raw_schema, start_index=0)
        counts = allgather_obj(part.num_rows)
        part.start_index = sum(counts[:pid])
        part.host_block = {"pid": pid, "nproc": nproc, "counts": counts}
        return [part]

    def iter_partitions(self, context, projection=None):
        """STREAMING read: yield partitions as Arrow record batches arrive,
        so take(n) touches only the file prefix it consumes (reference:
        range tasks over inputSplitSize, LocalBackend.cc:552-611).

        Structurally-invalid rows are yielded as one trailing fallback
        partition per file (position splicing needs a whole-file scan, which
        streaming exists to avoid); the eager load_partitions path keeps
        exact merge-in-order for them."""
        import pyarrow as pa
        import pyarrow.csv as pacsv

        stat = self.stat
        max_w = context.options_store.get_int("tuplex.tpu.maxStrBytes", 4096)
        out_columns = list(projection) if projection else stat.columns
        raw_schema = T.row_of(out_columns,
                              [T.option(T.STR)] * len(out_columns))
        proj_idx = [stat.columns.index(c) for c in out_columns]
        split = context.options_store.get_size(
            "tuplex.inputSplitSize", 1 << 22)
        read_opts = pacsv.ReadOptions(
            use_threads=True,
            block_size=max(1 << 14, min(split, 1 << 26)),
            column_names=stat.columns,
            skip_rows=1 if stat.has_header else 0,
            autogenerate_column_names=False)
        conv_opts = pacsv.ConvertOptions(
            column_types={c: pa.string() for c in stat.columns},
            include_columns=list(projection) if projection else None,
            strings_can_be_null=False)
        offset = 0
        for path in self.files:
            bad_rows: list[tuple[int, str]] = []

            def on_invalid(row, _bad=bad_rows):
                _bad.append((row.number or 0, row.text or ""))
                return "skip"

            parse_opts = pacsv.ParseOptions(
                delimiter=stat.delimiter,
                quote_char=getattr(stat, "quotechar", '"'),
                invalid_row_handler=on_invalid)
            file_rows = 0
            with pacsv.open_csv(_csv_input(path), read_options=read_opts,
                                parse_options=parse_opts,
                                convert_options=conv_opts) as reader:
                for batch in TR.pulls(reader, "ingest:read-csv", "io"):
                    if batch.num_rows == 0:
                        continue
                    with TR.span("ingest:to-partition", "io") as _sp:
                        _sp.set("rows", batch.num_rows)
                        p = _table_to_partition(
                            pa.Table.from_batches([batch]), raw_schema,
                            max_w, offset)
                    offset += p.num_rows
                    file_rows += p.num_rows
                    yield p
            # streamed: the counters move once the file's last batch is in
            # (a take() that stops early never read the whole file)
            _note_read(TR.NOOP, path, file_rows + len(bad_rows),
                       len(out_columns), len(stat.columns))
            if bad_rows:
                p = _bad_rows_partition(bad_rows, stat, proj_idx, raw_schema,
                                        offset)
                offset += p.num_rows
                yield p

    def _read_table(self, path: str, projection=None):
        """One file as an Arrow table of string columns, and the rows Arrow
        refused for their cell count ((number, text) pairs)."""
        import pyarrow as pa
        import pyarrow.csv as pacsv

        stat = self.stat
        bad_rows: list[tuple[int, str]] = []

        def on_invalid(row):
            bad_rows.append((row.number or 0, row.text or ""))
            return "skip"

        # Always read under the USER-FACING column names (skipping the header
        # line instead of parsing it): with user-overridden `columns=`, the
        # file's header names differ from stat.columns, and keying
        # include_columns / column_types by the wrong namespace raised
        # ArrowKeyError / silently skipped the read-as-string coercion
        # (advisor finding, round 1).
        read_opts = pacsv.ReadOptions(
            use_threads=True,
            block_size=1 << 24,
            column_names=stat.columns,
            skip_rows=1 if stat.has_header else 0,
            autogenerate_column_names=False)
        parse_opts = pacsv.ParseOptions(
            delimiter=stat.delimiter,
            quote_char=getattr(stat, "quotechar", '"'),
            invalid_row_handler=on_invalid)
        conv_opts = pacsv.ConvertOptions(
            column_types={c: pa.string() for c in stat.columns},
            include_columns=list(projection) if projection else None,
            strings_can_be_null=False)
        with TR.span("ingest:read-csv", "io") as _sp:
            table = pacsv.read_csv(_csv_input(path), read_options=read_opts,
                                   parse_options=parse_opts,
                                   convert_options=conv_opts)
            _note_read(_sp, path, table.num_rows + len(bad_rows),
                       table.num_columns, len(stat.columns))
        return table, bad_rows


def _planned_widths(tables: list, max_w: int) -> list[int]:
    """The byte width of every column's leaf over ALL of `tables`, from one
    pass over the Arrow offsets: the widest cell, capped at `max_w` (a
    longer cell boxes its row), in its q8 bucket."""
    import pyarrow as pa

    widest = [1] * tables[0].num_columns
    for table in tables:
        for ci, col in enumerate(table.columns):
            dt = np.int64 if pa.types.is_large_string(col.type) else np.int32
            for chunk in col.chunks:
                if len(chunk):
                    offs = np.frombuffer(chunk.buffers()[1], dtype=dt)[
                        chunk.offset: chunk.offset + len(chunk) + 1]
                    widest[ci] = max(widest[ci],
                                     int((offs[1:] - offs[:-1]).max()))
    return [C.bucket_size(max(min(w, max_w), 1), minimum=8) for w in widest]


def _cut_partitions(cuts: list, stat: "CSVStatistic", raw_schema: T.RowType,
                    proj_idx: list, max_w: int, widths: list):
    """The partitions `_planned_stream` planned, each built when pulled and
    at the planned `widths`; a file's table is let go with its last one."""
    base = 0
    while cuts:
        table, scanned, bad_rows, cap, sizes = cuts.pop(0)
        n = table.num_rows
        if scanned is not None:
            yield from TR.pulls(_spliced_partitions(
                table, scanned, raw_schema, proj_idx, max_w, cap, base,
                widths), "ingest:to-partition", "io")
            base += n + len(scanned)
            continue
        start = 0
        for m in sizes:
            with TR.span("ingest:to-partition", "io") as _sp:
                _sp.set("rows", m)
                part = _table_to_partition(table.slice(start, m), raw_schema,
                                           max_w, base + start, widths)
            yield part
            start += m
        if bad_rows:
            yield _bad_rows_partition(bad_rows, stat, proj_idx, raw_schema,
                                      base + n, widths, max_w)
        base += n + len(bad_rows)


def _note_read(sp, path: str, rows: int, columns: int,
               file_columns: int) -> None:
    """One file is read: its size, rows, the columns read and the columns
    the file has go on the `ingest:read-csv` span, and its size and rows
    into the `ingest_*` counters (always on, like the transfer counters)."""
    try:
        nbytes = VirtualFileSystem.file_size(path)
    except Exception:
        nbytes = 0
    sp.set("bytes", nbytes).set("rows", rows).set("columns", columns) \
      .set("file_columns", file_columns)
    xferstats.bump("ingest_bytes", nbytes)
    xferstats.bump("ingest_rows", rows)
    xferstats.bump("ingest_files", 1)


def _bad_rows_partition(bad_rows: list, stat: "CSVStatistic",
                        proj_idx: list, raw_schema: T.RowType,
                        start_index: int, widths=None,
                        max_w: int = 0) -> C.Partition:
    """Trailing partition of leniently re-parsed structurally-bad rows
    (shared by the eager fallback and streaming paths). With `widths` (a
    planned stream's, and its `max_w`) the leaves take those widths, and a
    cell wider than its column's boxes its row."""
    vals = []
    for _, text in bad_rows:
        try:
            cells = next(_pycsv.reader(
                [text], delimiter=stat.delimiter,
                quotechar=getattr(stat, "quotechar", '"')))
        except Exception:
            cells = [text]
        vals.append(tuple(cells[i] if i < len(cells) else None
                          for i in proj_idx))
    if widths is None:
        return C.build_partition(vals, raw_schema, start_index=start_index)
    import pyarrow as pa

    table = pa.table([pa.array([v[ci] for v in vals], pa.string())
                      for ci in range(len(proj_idx))],
                     names=[str(ci) for ci in range(len(proj_idx))])
    return _table_to_partition(table, raw_schema, max_w, start_index, widths)


def _scan_bad_records(path: str, stat: "CSVStatistic", text=None,
                      skip_header=None) -> list[tuple[int, list]]:
    """[(data-row ordinal, cells)] for records whose cell count != k —
    python-csv replica of Arrow's invalid-row criterion, used to recover the
    original positions Arrow doesn't report. Ordinals count ALL non-empty
    data records (good + bad) in file order, excluding the header.
    `text` scans a fragment instead of the file (host-sharded reads)."""
    k = stat.num_columns
    out: list[tuple[int, list]] = []
    if text is None:
        with VirtualFileSystem.open_read(path, "rb") as fp:
            text = fp.read().decode("utf-8", errors="replace")
    ordinal = 0
    skip_header = stat.has_header if skip_header is None else skip_header
    for rec in _pycsv.reader(_io.StringIO(text), delimiter=stat.delimiter,
                             quotechar=getattr(stat, "quotechar", '"')):
        if not rec:
            continue  # blank line: Arrow skips it too
        if skip_header:
            skip_header = False
            continue
        if len(rec) != k:
            out.append((ordinal, rec))
        ordinal += 1
    return out


def _spliced_partitions(table, scanned: list, raw_schema: T.RowType,
                        proj_idx: list[int], max_w: int, rows_per_part: int,
                        base_index: int, widths=None):
    """Partitions over the ORIGINAL row-ordinal space: surviving Arrow rows
    keep their true slots, structurally-bad rows occupy theirs as boxed
    fallback slots (normal_mask False -> interpreter path). `widths`: the
    planned leaf widths (`_table_to_partition`)."""
    n = table.num_rows
    nb = len(scanned)
    bad_ord = np.asarray([o for o, _ in scanned], dtype=np.int64)
    boxed = [tuple(cells[i] if i < len(cells) else None for i in proj_idx)
             for _, cells in scanned]
    total = n + nb
    # original ordinal of the j-th surviving row: j + |{i : bad_ord[i]-i <= j}|
    surv = np.arange(n, dtype=np.int64) + np.searchsorted(
        bad_ord - np.arange(nb), np.arange(n), side="right")
    start = 0
    for m in _chunk_sizes(total, rows_per_part):
        j0, j1 = np.searchsorted(surv, [start, start + m])
        bi0, bi1 = np.searchsorted(bad_ord, [start, start + m])
        tp = _table_to_partition(table.slice(int(j0), int(j1 - j0)),
                                 raw_schema, max_w, base_index + start,
                                 widths)
        if bi1 == bi0:
            yield tp  # no bad slots here: chunk is contiguous, j1-j0 == m
        else:
            pos = surv[j0:j1] - start
            gp = C.gather_partition(tp, pos, np.arange(j1 - j0), m)
            gp.start_index = base_index + start
            mask = np.ones(m, np.bool_)
            if tp.normal_mask is not None:
                mask[pos] = tp.normal_mask
            fb = {int(pos[i]): v for i, v in tp.fallback.items()}
            for o, bx in zip(bad_ord[bi0:bi1].tolist(), boxed[bi0:bi1]):
                mask[o - start] = False
                fb[o - start] = bx
            gp.normal_mask = mask
            gp.fallback = fb
            yield gp
        start += m


def _csv_input(path: str):
    """Path for local files, a file-like from the VFS for remote URIs —
    pyarrow.csv accepts both."""
    if VirtualFileSystem._scheme(path) == "file":
        return path
    return VirtualFileSystem.open_read(path)


def _csv_rows_per_partition(context, table) -> int:
    psize = context.options_store.get_size("tuplex.partitionSize", 32 << 20)
    per_row = max(16, table.nbytes // max(table.num_rows, 1) * 2)
    return max(256, int(psize // per_row))


def _chunk_sizes(total: int, cap: int) -> list[int]:
    """Balanced partition sizes: a near-cap total otherwise yields a tiny
    tail partition whose fixed dispatch cost dwarfs its rows. Absorb a small tail entirely
    (within +25% of cap), else ceil-divide into equal chunks."""
    if total <= 0:
        return []
    if total <= cap + cap // 4:
        return [total]
    import math

    k = math.ceil(total / cap)
    base, rem = divmod(total, k)
    return [base + (1 if i < rem else 0) for i in range(k)]


def _table_to_partition(table, schema: T.RowType, max_w: int,
                        start_index: int, widths=None) -> C.Partition:
    """Arrow string columns -> fixed-width byte-matrix leaves, vectorized.

    Over-long cells (>{max_w}B) force their row to the boxed fallback path.
    `widths` (one a column) builds each leaf at that width directly, so
    that nothing pads it later; a cell wider than its column's width boxes
    its row too. Without it a leaf is as wide as the slice's widest cell.
    """
    n = table.num_rows
    cut = _native_leaves(table, n, max_w, widths) if widths and n else None
    leaves, too_long_rows = cut if cut is not None else \
        _leaves_by_column(table, n, max_w, widths)
    part = C.Partition(schema=schema, num_rows=n, leaves=leaves,
                       start_index=start_index)
    if too_long_rows is not None and too_long_rows.any():
        fallback = {}
        for i in np.nonzero(too_long_rows)[0].tolist():
            fallback[i] = tuple(col[i].as_py() for col in table.columns)
        part.normal_mask = ~too_long_rows
        part.fallback = fallback
    return part


def _leaves_by_column(table, n: int, max_w: int, widths):
    """(leaves, the rows a too-long cell boxes) of an Arrow table slice,
    a column at a time: contiguous large_string first, then
    `C.arrow_string_to_leaf` (native loop or numpy gather)."""
    import pyarrow as pa

    leaves: dict[str, C.Leaf] = {}
    too_long_rows = np.zeros(n, dtype=np.bool_)
    for ci in range(table.num_columns):
        arr = table.column(ci).combine_chunks()
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        arr = arr.cast(pa.large_string())
        valid = np.ones(n, dtype=np.bool_)
        if arr.null_count:
            valid = np.asarray(arr.is_valid())
        leaf, full_lens = C.arrow_string_to_leaf(
            arr, n, max_w, valid, return_full_lens=True,
            width=widths[ci] if widths else 0)
        # rows with over-long cells keep their slot but box via fallback
        too_long_rows |= full_lens > (min(max_w, widths[ci]) if widths
                                      else max_w)
        leaves[str(ci)] = leaf
    return leaves, too_long_rows


def _native_leaves(table, n: int, max_w: int, widths):
    """Every leaf of an Arrow table slice at its planned width from ONE
    native call (`cut_strings`), straight off the chunks the slice touches:
    no `combine_chunks`, no offsets cast, and the interpreter lock let go
    once a partition — on the prefetch thread each such handoff queues
    behind the job thread for up to a switch interval, and the per-leaf
    route makes four a leaf (numpy lets the lock go too, to fill or
    reduce an array of this size: the validity masks are made without
    it). Returns (leaves, the rows a too-long cell boxes or None for no
    such row), or None where the per-leaf route has to do it (no native
    module, a column with nulls or of another type)."""
    import pyarrow as pa

    from ..native import get as _native_get

    nat = _native_get()
    if nat is None or not hasattr(nat, "cut_strings"):
        return None
    cols = []
    for col, w in zip(table.columns, widths):
        large = pa.types.is_large_string(col.type)
        if col.null_count or not (large or pa.types.is_string(col.type)):
            return None
        pieces = []
        for chunk in col.chunks:
            if len(chunk):
                bufs = chunk.buffers()
                pieces.append((bufs[2] if bufs[2] else b"", bufs[1],
                               chunk.offset, len(chunk), large))
        cols.append((pieces, w))
    mats, over = nat.cut_strings(cols, n, max_w)
    # a buffer a leaf, as the per-leaf route leaves them: one buffer a
    # partition with the leaves as views of it read 22% for q19 where this
    # reads 29% (staging from the views is dearer; PERF.md, PR 32)
    leaves = {str(ci): C.StrLeaf(
        np.frombuffer(mat, dtype=np.uint8).reshape(n, w),
        np.frombuffer(lens, dtype=np.int32),
        np.frombuffer(bytearray(b"\x01") * n, dtype=np.bool_))
        for ci, ((mat, lens), w) in enumerate(zip(mats, widths))}
    return leaves, None if over is None else \
        np.frombuffer(over, dtype=np.bool_)


class TextSourceOperator(L.LogicalOperator):
    """One row per line (reference: logical FileInputOperator text mode +
    physical/TextReader.cc)."""

    def __init__(self, options, pattern: str, files: list[str],
                 null_values: Optional[Sequence[str]] = None):
        super().__init__([])
        self.pattern = pattern
        self.files = files
        self.null_values = tuple(null_values) if null_values else ()
        self._schema = T.row_of(
            ["_0"], [T.option(T.STR) if self.null_values else T.STR])
        self._sample_lines: Optional[list[str]] = None

    def _null_map(self, lines):
        if not self.null_values:
            return lines
        nv = set(self.null_values)
        return [None if ln in nv else ln for ln in lines]

    def source_key(self):
        return files_fingerprint(self.files,
                                 extra=(self.pattern, self.null_values))

    def schema(self) -> T.RowType:
        return self._schema

    def sample(self) -> list[Row]:
        if self._sample_lines is None:
            lines: list[str] = []
            for f in self.files[:1]:
                with VirtualFileSystem.open_read(f, "rb") as fp:
                    chunk = fp.read(256 << 10).decode("utf-8",
                                                      errors="replace")
                lines = chunk.splitlines()[:1000]
            self._sample_lines = lines
        return [Row((ln,), None)
                for ln in self._null_map(self._sample_lines)]

    def _host_sharded(self, context) -> bool:
        """Per-host byte-range reads apply under REAL multi-process SPMD on
        a single-file source (reference analog: per-worker S3 input ranges,
        AWSLambdaBackend.cc:410-430). Option-gated; everything else reads
        whole files."""
        return _host_sharded_gate(self.files, context)

    def load_partitions(self, context, projection=None) -> list[C.Partition]:
        if self._host_sharded(context):
            import jax

            from ..parallel.hostio import allgather_obj, \
                read_text_lines_range

            pid, nproc = jax.process_index(), jax.process_count()
            lines = self._null_map(
                read_text_lines_range(self.files[0], pid, nproc))
            counts = allgather_obj(len(lines))
            part = C.build_partition(lines, self._schema,
                                     start_index=sum(counts[:pid]))
            part.host_block = {"pid": pid, "nproc": nproc,
                               "counts": counts}
            return [part]
        parts = []
        offset = 0
        for f in self.files:
            with VirtualFileSystem.open_read(f, "rb") as fp:
                text = fp.read().decode("utf-8", errors="replace")
            lines = self._null_map(text.splitlines())
            psize = context.options_store.get_size(
                "tuplex.partitionSize", 32 << 20)
            rows_pp = max(256, psize // 64)
            for s in range(0, len(lines), rows_pp):
                chunk = lines[s: s + rows_pp]
                parts.append(C.build_partition(chunk, self._schema,
                                               start_index=offset + s))
            offset += len(lines)
        return parts


# ---------------------------------------------------------------------------
# factory
# ---------------------------------------------------------------------------

_STAT_CACHE: dict = {}          # (file sig, sniff params) -> CSVStatistic
_STAT_CACHE_CAP = 64


def _file_sig(path: str):
    """Stat identity when cheaply stat-able; None => uncacheable."""
    return files_fingerprint([path])


def make_csv_operator(options, pattern: str, columns=None, header=None,
                      delimiter=None, type_hints=None, null_values=None,
                      quotechar: Optional[str] = None):
    if quotechar is None:
        quotechar = options.get_str("tuplex.csv.quotechar", '"') or '"'
    files = VirtualFileSystem.glob_input(pattern)
    if not files:
        raise TuplexException(f"no files match {pattern!r}")
    max_sample = options.get_size("tuplex.csv.maxDetectionMemory", 256 << 10)
    if null_values is None:
        null_values = DEFAULT_NULL_VALUES
    # sniffing an unchanged file with unchanged params is deterministic:
    # memoize so re-planned pipelines (repeat actions, benchmarks) skip the
    # sample read + type inference (reference re-runs CSVStatistic per plan)
    with TR.span("ingest:sniff", "io") as _sp:
        sig = _file_sig(files[0])
        skey = None
        stat = None
        if sig is not None:
            skey = (sig, max_sample, delimiter, header, quotechar,
                    tuple(null_values),
                    tuple(columns) if columns else None,
                    tuple(sorted(type_hints.items())) if type_hints
                    else None,
                    options.get_float("tuplex.normalcaseThreshold", 0.9),
                    options.get_int("tuplex.csv.maxDetectionRows", 1000))
            stat = _STAT_CACHE.get(skey)
        _sp.set("cached", int(stat is not None))
        if stat is None:
            with VirtualFileSystem.open_read(files[0], "rb") as fp:
                sample = fp.read(max_sample)
                evidence = _evidence_windows(fp, files[0], max_sample)
            _sp.set("bytes", len(sample) + sum(map(len, evidence)))
            stat = CSVStatistic(sample, options, delimiter=delimiter,
                                header=header, null_values=null_values,
                                columns=columns, type_hints=type_hints,
                                quotechar=quotechar, evidence=evidence)
            if skey is not None:
                if len(_STAT_CACHE) >= _STAT_CACHE_CAP:
                    _STAT_CACHE.pop(next(iter(_STAT_CACHE)))
                _STAT_CACHE[skey] = stat
    src = CSVSourceOperator(options, pattern, stat, files)
    return L.DecodeOperator(src, _decoded_schema(stat), stat.null_values,
                            general=T.row_of(stat.columns,
                                             stat.general_types))


_EVIDENCE_WINDOWS = 15


def _evidence_windows(fp, path: str, window: int) -> list:
    """`_EVIDENCE_WINDOWS` more windows of `window` bytes, evenly spaced
    over the file behind its head, for the general-case types alone
    (`widen_general_types`); a file that small is read whole. The normal
    case and the sample the UDFs are traced on stay the head's."""
    try:
        size = VirtualFileSystem.file_size(path)
        n = _EVIDENCE_WINDOWS
        if size <= window:
            return []
        if size <= (n + 2) * window:
            fp.seek(window - 1)             # one byte back: a whole line
            return [fp.read()]
        out = []
        for i in range(1, n + 1):           # the last one ends the file
            fp.seek(window + (size - 2 * window) * i // n)
            out.append(fp.read(window))
        return out
    except (OSError, AttributeError, ValueError):
        return []                           # a source that cannot seek


def _decoded_schema(stat: CSVStatistic) -> T.RowType:
    return T.row_of(stat.columns, stat.types)


def make_text_operator(options, pattern: str, null_values=None):
    files = VirtualFileSystem.glob_input(pattern)
    if not files:
        raise TuplexException(f"no files match {pattern!r}")
    return TextSourceOperator(options, pattern, files,
                              null_values=null_values)
