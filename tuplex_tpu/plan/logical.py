"""Logical operator DAG.

Re-designs the reference's logical layer (reference: core/src/logical/ — one
class per operator with output-schema inference and sampling,
LogicalOperator.cc:39-50 compute()). Schema inference here IS the sample
tracer: operators run their UDF on the parent's sample rows via CPython
(reference: TraceVisitor semantics — execute on sample to annotate types,
core/include/TraceVisitor.h:25-80) and speculate the normal-case output type.
"""

from __future__ import annotations

import itertools
from typing import Any, Callable, Optional, Sequence

from ..core import typesys as T
from ..core.errors import TuplexException
from ..core.row import Row
from ..utils.reflection import UDFSource, get_udf_source

_op_ids = itertools.count(1)

# cross-job memo: chain identity -> sample rows / inferred schema. Rebuilding
# a content-identical pipeline over fingerprintable sources skips re-running
# every UDF over the sample (the reference reuses per-UDF hint results the
# same way via its source_vault + JIT cache keying). LRU-bounded: the old
# grow-then-.clear() pattern dropped every warm schema the moment one insert
# crossed the cap (utils/lru.py).
from ..utils.lru import LruDict

_cross_job_samples: LruDict = LruDict(256)
_cross_job_branchprofs: LruDict = LruDict(256)
_cross_job_schemas: LruDict = LruDict(512)


SAMPLE_EXC_CAP = 16   # recorder slices to tuplex.webui.exceptionDisplayLimit


def record_sample_exc(op: "LogicalOperator", e: Exception, row) -> None:
    """Sample-time exception preview (reference: SampleProcessor running
    sample rows through real UDFs to give the webui per-operator exception
    previews, include/physical/SampleProcessor.h:26-103). Deduplicated and
    capped, attached to the operator, surfaced via the job recorder (the
    same row fails in both schema inference AND sampling — one entry)."""
    lst = getattr(op, "sample_exceptions", None)
    if lst is None:
        lst = op.sample_exceptions = []
    entry = (type(e).__name__, repr(getattr(row, "values", row))[:200])
    if len(lst) < SAMPLE_EXC_CAP and entry not in lst:
        lst.append(entry)


def apply_udf_python(udf: UDFSource, row: Row, func=None) -> Any:
    """Interpreter-path calling convention shared by sampling and the
    fallback pipeline (reference: PythonPipelineBuilder's generated Row class,
    core/src/physical/PythonPipelineBuilder.cc:1-60). `func` substitutes an
    instrumented clone of the UDF (branch profiling) under the same
    convention."""
    f = func if func is not None else udf.func
    nparams = len(udf.params) if udf.params else 1
    if nparams > 1 and len(row.values) == nparams:
        return f(*row.values)
    if row.columns is not None:
        return f(row)
    if len(row.values) == 1:
        return f(row.values[0])
    return f(tuple(row.values))


class LogicalOperator:
    """Base: parent links + output schema + sample rows."""

    def __init__(self, parents: Sequence["LogicalOperator"]):
        self.id = next(_op_ids)
        self.parents = list(parents)
        self.name = type(self).__name__.replace("Operator", "").lower()

    @property
    def parent(self) -> "LogicalOperator":
        return self.parents[0]

    # -- overridables --------------------------------------------------------
    def schema(self) -> T.RowType:
        raise NotImplementedError

    def columns(self) -> Optional[tuple[str, ...]]:
        from ..runtime.columns import user_columns

        return user_columns(self.schema())

    def sample(self) -> list[Row]:
        raise NotImplementedError

    def source_key(self) -> Optional[str]:
        """Content identity of a SOURCE operator's data, or None when the
        data has no cheap stable fingerprint (e.g. parallelize over a live
        python list). Non-None keys enable the cross-job sample/schema memo:
        rebuilding the identical pipeline (the bench builds a fresh DataSet
        per run; the reference JIT-caches per stage the same way) skips
        re-running every UDF over the sample."""
        return None

    def chain_key(self) -> Optional[str]:
        """Content identity of this operator INCLUDING its whole upstream
        chain; None disables cross-job memoization for this subtree."""
        ck = getattr(self, "_chain_key_memo", False)
        if ck is not False:
            return ck
        from ..compiler.analyzer import op_nondeterministic

        if op_nondeterministic(self):
            # purity gate (compiler/analyzer.py): a nondeterministic UDF
            # (random/time) makes content identity meaningless — rebuilding
            # the pipeline must re-run its samples, not reuse memoized ones
            self._chain_key_memo = None
            return None
        import hashlib

        from .physical import _op_identity

        h = hashlib.sha256()
        if not self.parents:
            sk = self.source_key()
            if sk is None:
                self._chain_key_memo = None
                return None
            h.update(sk.encode())
        for p in self.parents:
            pk = p.chain_key()
            if pk is None:
                self._chain_key_memo = None
                return None
            h.update(pk.encode())
        h.update(_op_identity(self).encode())
        ck = self._chain_key_memo = h.hexdigest()[:24]
        return ck

    def cached_sample(self) -> list[Row]:
        """Memoized sample(): every consumer (child schema inference, child
        samples, speculation probes) shares ONE trace per operator instead of
        re-running the whole upstream UDF chain per call — planning was
        measurably O(ops²) in sample applications without this (reference:
        TraceVisitor runs once per operator too). Content-identical chains
        over fingerprintable sources additionally share across jobs."""
        memo = getattr(self, "_sample_memo", None)
        if memo is None:
            ck = self.chain_key()
            hit = _cross_job_samples.get(ck) if ck is not None else None
            if hit is not None:
                memo, excs = hit
                if excs:   # previews travel with the memo: a rebuilt
                    # identical pipeline skips the UDF re-runs but must
                    # still show its sample exceptions
                    self.sample_exceptions = list(excs)
            else:
                memo = self.sample()
                if ck is not None:
                    _cross_job_samples[ck] = (
                        memo, list(getattr(self, "sample_exceptions", [])))
            self._sample_memo = memo
        return memo

    def is_breaker(self) -> bool:
        """Pipeline breaker => stage boundary (reference:
        PhysicalPlan.cc:60-238 — joins/aggregates end stages)."""
        return False

    def __repr__(self):
        return f"{type(self).__name__}(#{self.id})"


class ParallelizeOperator(LogicalOperator):
    """In-memory input (reference: core/src/logical/ParallelizeOperator.cc)."""

    def __init__(self, data: list, schema: T.RowType, sample_size: int = 256):
        super().__init__([])
        self.data = data
        self._schema = schema
        self._sample_size = sample_size

    def schema(self) -> T.RowType:
        return self._schema

    def sample(self) -> list[Row]:
        from ..runtime.columns import user_columns

        cols = user_columns(self._schema)
        return [Row.from_value(v, cols) for v in self.data[: self._sample_size]]


class UDFOperator(LogicalOperator):
    """Base for operators carrying a UDF (reference: logical/UDFOperator.cc)."""

    def __init__(self, parent: LogicalOperator, func: Callable):
        super().__init__([parent])
        self.udf = get_udf_source(func)
        self._schema_cache: Optional[T.RowType] = None

    def branch_profile(self) -> dict:
        """Which if/else arms the operator's sample observed (reference:
        TraceVisitor branch annotations feeding RemoveDeadBranchesVisitor).
        Keyed by (kind, lineno, col) of the udf.tree node; memoized — the
        instrumented re-run costs one python pass over the sample."""
        memo = getattr(self, "_branch_prof_memo", None)
        if memo is None:
            from ..compiler.analyzer import op_analysis

            rep = op_analysis(self)
            if rep is not None and not rep.deterministic:
                # purity gate: a nondeterministic UDF's sample run is not
                # representative of execution — pruning arms it happened
                # not to take would bounce live rows to the interpreter
                self._branch_prof_memo = {}
                return {}
            ck = self.chain_key()
            hit = _cross_job_branchprofs.get(ck) if ck is not None else None
            if hit is not None:
                memo = hit
            else:
                from ..compiler.branchprof import profile_branches

                # (too few trials of a test call no arm dead:
                # branchprof.MIN_TRIALS)
                memo = profile_branches(
                    self.udf, self.parent.cached_sample(),
                    self._profile_call)
                if ck is not None:
                    _cross_job_branchprofs[ck] = memo
            self._branch_prof_memo = memo
        return memo

    def _profile_call(self, f, row) -> None:
        apply_udf_python(self.udf, row, func=f)

    def schema(self) -> T.RowType:
        if self._schema_cache is None:
            ck = self.chain_key()
            if ck is not None:
                hit = _cross_job_schemas.get(ck)
                if hit is not None:
                    self._schema_cache = hit
                    return hit
            # sample-free specialization (compiler/typeinfer.py): when the
            # abstract interpreter decides the output type EXACTLY from the
            # UDF's AST, skip the CPython sample trace entirely. The static
            # verdict is sound w.r.t. the trace (mismatch ⇒ widened to
            # undecidable ⇒ None here), so memo keys/values stay compatible
            # with traced runs.
            from ..compiler.typeinfer import static_op_schema

            static = static_op_schema(self)
            if static is not None:
                from ..compiler.analyzer import STATS

                STATS["sample_traces_skipped"] += 1
                # the webui's sample exception previews were a side effect
                # of the trace this skips; the recorder re-runs them on
                # demand (preview_sample_exceptions) only when enabled
                self._sample_trace_skipped = True
                self._schema_cache = static
            else:
                self._schema_cache = self._infer_schema()
            if ck is not None:
                _cross_job_schemas[ck] = self._schema_cache
        return self._schema_cache

    def _infer_schema(self) -> T.RowType:
        raise NotImplementedError


def _output_type(outs: list) -> T.Type:
    """The normal-case type of a UDF's sampled results. A `None` rarer than
    `1 - normalcaseThreshold` of them is a deviant row (it resolves on the
    general tier), so the type does not turn on one sampled row in a
    thousand."""
    return T.normal_case_type(outs, rare_nulls_deviate=True)[0]


class MapOperator(UDFOperator):
    def _infer_schema(self) -> T.RowType:
        outs = []
        for r in self.parent.cached_sample():
            try:
                outs.append(apply_udf_python(self.udf, r))
            except Exception as e:
                record_sample_exc(self, e, r)
        if not outs:
            # UDF failed on EVERY sample row: job still runs, all rows become
            # exception rows (schema degrades to pyobject)
            return T.row_of(["_0"], [T.PYOBJECT])
        if all(isinstance(o, tuple) for o in outs) and outs and \
                len({len(o) for o in outs}) == 1:
            k = len(outs[0])
            types = [_output_type([o[i] for o in outs])
                     for i in range(k)]
            return T.row_of([f"_{i}" for i in range(k)], types)
        # dict results keep column names (reference: map with dict output)
        if all(isinstance(o, dict) for o in outs) and outs:
            keys = list(outs[0].keys())
            if all(list(o.keys()) == keys for o in outs):
                types = [_output_type([o[k] for o in outs])
                         for k in keys]
                return T.row_of(keys, types)
        nc = _output_type(outs)
        return T.row_of(["_0"], [nc])

    def sample(self) -> list[Row]:
        out = []
        cols = self.columns()
        for r in self.parent.cached_sample():
            try:
                v = apply_udf_python(self.udf, r)
            except Exception as e:
                record_sample_exc(self, e, r)
                continue
            if isinstance(v, dict):
                out.append(Row(list(v.values()), list(v.keys())))
            else:
                out.append(Row.from_value(v, cols))
        return out


class FilterOperator(UDFOperator):
    def _infer_schema(self) -> T.RowType:
        return self.parent.schema()

    def columns(self):
        return self.parent.columns()

    def sample(self) -> list[Row]:
        out = []
        for r in self.parent.cached_sample():
            try:
                if apply_udf_python(self.udf, r):
                    out.append(r)
            except Exception as e:
                record_sample_exc(self, e, r)
        return out


class WithColumnOperator(UDFOperator):
    """Adds or replaces a named column (reference: logical/WithColumnOperator.cc)."""

    def __init__(self, parent: LogicalOperator, column: str, func: Callable):
        self.column = column
        super().__init__(parent, func)

    def _infer_schema(self) -> T.RowType:
        from ..runtime.columns import user_columns

        ps = self.parent.schema()
        if user_columns(ps) is None:
            raise TuplexException("withColumn requires named columns")
        outs = []
        for r in self.parent.cached_sample():
            try:
                outs.append(apply_udf_python(self.udf, r))
            except Exception as e:
                record_sample_exc(self, e, r)
        nc = T.PYOBJECT if not outs else _output_type(outs)
        cols = list(ps.columns)
        types = list(ps.types)
        if self.column in cols:
            types[cols.index(self.column)] = nc
        else:
            cols.append(self.column)
            types.append(nc)
        return T.row_of(cols, types)

    def sample(self) -> list[Row]:
        schema = self.schema()
        out = []
        for r in self.parent.cached_sample():
            try:
                v = apply_udf_python(self.udf, r)
            except Exception as e:
                record_sample_exc(self, e, r)
                continue
            d = dict(zip(r.columns, r.values))
            d[self.column] = v
            out.append(Row([d[c] for c in schema.columns], schema.columns))
        return out


class MapColumnOperator(UDFOperator):
    """UDF over ONE column's value (reference: logical/MapColumnOperator.cc)."""

    def __init__(self, parent: LogicalOperator, column: str, func: Callable):
        self.column = column
        super().__init__(parent, func)

    def _infer_schema(self) -> T.RowType:
        ps = self.parent.schema()
        if self.column not in (ps.columns or ()):
            raise TuplexException(f"unknown column {self.column!r}")
        ci = ps.columns.index(self.column)
        outs = []
        for r in self.parent.cached_sample():
            try:
                outs.append(self.udf.func(r.values[ci]))
            except Exception as e:
                record_sample_exc(self, e, r)
        nc = T.PYOBJECT if not outs else _output_type(outs)
        types = list(ps.types)
        types[ci] = nc
        return T.row_of(ps.columns, types)

    def sample(self) -> list[Row]:
        ps = self.parent.schema()
        ci = ps.columns.index(self.column)
        out = []
        for r in self.parent.cached_sample():
            try:
                v = self.udf.func(r.values[ci])
            except Exception as e:
                record_sample_exc(self, e, r)
                continue
            vals = list(r.values)
            vals[ci] = v
            out.append(Row(vals, r.columns))
        return out

    def _profile_call(self, f, row) -> None:
        ci = getattr(self, "_prof_ci", None)
        if ci is None:
            ci = self._prof_ci = \
                self.parent.schema().columns.index(self.column)
        f(row.values[ci])


class SelectColumnsOperator(LogicalOperator):
    def __init__(self, parent: LogicalOperator, columns: Sequence):
        super().__init__([parent])
        self.selected = list(columns)

    def _resolve_indices(self) -> list[int]:
        ps = self.parent.schema()
        idx = []
        for c in self.selected:
            if isinstance(c, int):
                idx.append(c if c >= 0 else len(ps.types) + c)
            else:
                if c not in ps.columns:
                    raise TuplexException(f"unknown column {c!r}")
                idx.append(ps.columns.index(c))
        return idx

    def schema(self) -> T.RowType:
        ps = self.parent.schema()
        idx = self._resolve_indices()
        return T.row_of([ps.columns[i] for i in idx],
                        [ps.types[i] for i in idx])

    def sample(self) -> list[Row]:
        idx = self._resolve_indices()
        s = self.schema()
        return [Row([r.values[i] for i in idx], s.columns)
                for r in self.parent.cached_sample()]


class RenameColumnOperator(LogicalOperator):
    def __init__(self, parent: LogicalOperator, old, new: str):
        super().__init__([parent])
        self.old = old
        self.new = new

    def schema(self) -> T.RowType:
        ps = self.parent.schema()
        if isinstance(self.old, int):
            i = self.old
        else:
            if self.old not in (ps.columns or ()):
                raise TuplexException(f"unknown column {self.old!r}")
            i = ps.columns.index(self.old)
        cols = list(ps.columns)
        cols[i] = self.new
        return T.row_of(cols, ps.types)

    def sample(self) -> list[Row]:
        s = self.schema()
        return [Row(r.values, s.columns) for r in self.parent.cached_sample()]


class ResolveOperator(LogicalOperator):
    """Attaches an exception resolver to the previous operator (reference:
    logical/ResolveOperator.cc; dataset.py:162)."""

    def __init__(self, parent: LogicalOperator, exc_class: type, func: Callable):
        super().__init__([parent])
        self.exc_class = exc_class
        self.udf = get_udf_source(func)

    def schema(self) -> T.RowType:
        return self.parent.schema()

    def sample(self) -> list[Row]:
        return self.parent.cached_sample()


class IgnoreOperator(LogicalOperator):
    """Silently drops rows raising exc_class at the previous operator
    (reference: logical/IgnoreOperator.cc; dataset.py:319)."""

    def __init__(self, parent: LogicalOperator, exc_class: type):
        super().__init__([parent])
        self.exc_class = exc_class

    def schema(self) -> T.RowType:
        return self.parent.schema()

    def sample(self) -> list[Row]:
        return self.parent.cached_sample()


class TakeOperator(LogicalOperator):
    def __init__(self, parent: LogicalOperator, limit: int):
        super().__init__([parent])
        self.limit = limit

    def schema(self) -> T.RowType:
        return self.parent.schema()

    def sample(self) -> list[Row]:
        s = self.parent.cached_sample()
        return s if self.limit < 0 else s[: self.limit]


class DecodeOperator(LogicalOperator):
    """Typed decode of raw string cells against the speculated normal-case
    schema — fused into the stage so parsing runs on device (reference:
    JITCSVSourceTaskBuilder / CSVParseRowGenerator fuse parse into the
    pipeline). The interpreter path implements the GENERAL case: cells that
    fail the normal-case parse stay raw strings, exactly like the reference's
    general-case row type preserves un-specialized columns."""

    def __init__(self, parent: LogicalOperator, declared: T.RowType,
                 null_values: Sequence[str],
                 general: "Optional[T.RowType]" = None):
        super().__init__([parent])
        self.declared = declared
        self.null_values = tuple(null_values)
        # general-case row type (supertype of the sample): the compiled
        # middle tier decodes under THESE types so normal-case violations
        # stay vectorized (reference: StageBuilder.cc:1145
        # generateResolveCodePath over the general-case schema)
        self.general = general if general is not None and \
            general.name != declared.name else None

    def schema(self) -> T.RowType:
        return self.declared

    def sample(self) -> list[Row]:
        out = []
        cols = self.declared.columns
        sel = None   # parent-row indices when this decode is projection-
        # pruned: the parent sample still carries the FULL source row, so
        # cells must be selected by name — a positional zip would silently
        # decode the wrong columns (and feed garbage to every downstream
        # sample, e.g. filter selectivities of 0 for compaction planning)
        for r in self.parent.cached_sample():
            if sel is None:
                if cols and r.columns and tuple(r.columns) != tuple(cols) \
                        and all(c in r.columns for c in cols):
                    sel = [r.columns.index(c) for c in cols]
                else:
                    sel = []
            vin = [r.values[i] for i in sel] if sel else r.values
            vals = [decode_cell_python(v, t, self.null_values)
                    for v, t in zip(vin, self.declared.types)]
            out.append(Row(vals, cols))
        return out


def preview_sample_exceptions(op) -> list:
    """Sample exception previews for the webui, run ON DEMAND for operators
    whose schema came from the static verdict (sample-free specialization
    skips the trace whose side effect they were). Reference-faithful: the
    SampleProcessor runs only when the history server is attached, so the
    recorder — not schema inference — pays for previews. No-op for
    operators the trace (or a memo hit) already populated."""
    if not getattr(op, "_sample_trace_skipped", False) \
            or getattr(op, "sample_exceptions", None) is not None:
        return list(getattr(op, "sample_exceptions", []) or [])
    try:
        rows = op.parent.cached_sample()
        if isinstance(op, MapColumnOperator):
            ci = op.parent.schema().columns.index(op.column)
            for r in rows:
                try:
                    op.udf.func(r.values[ci])
                except Exception as e:
                    record_sample_exc(op, e, r)
        else:
            for r in rows:
                try:
                    apply_udf_python(op.udf, r)
                except Exception as e:
                    record_sample_exc(op, e, r)
    except Exception:   # pragma: no cover - previews are advisory
        pass
    if getattr(op, "sample_exceptions", None) is None:
        # mark the pass done even when nothing raised — record_sample_exc
        # only creates the list on an exception, and without the marker
        # every later job would re-run the whole sample per clean UDF
        op.sample_exceptions = []
    return list(op.sample_exceptions)


def decode_cell_python(cell, t: T.Type, null_values) -> Any:
    """General-case decode: normal-case parse if possible, else the raw
    string survives (so downstream interpreter UDFs can still handle it)."""
    if cell is None:
        return None
    if not isinstance(cell, str):
        return cell
    if cell in null_values:
        return None
    base = t.without_option() if t.is_optional() else t
    try:
        if base is T.I64:
            return int(cell)
        if base is T.F64:
            return float(cell)
        if base is T.BOOL:
            low = cell.strip().lower()
            if low == "true":
                return True
            if low == "false":
                return False
            return cell
    except ValueError:
        return cell
    return cell
