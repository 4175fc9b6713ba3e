"""Logical optimizations: projection pushdown into file sources.

Re-designs the reference's logical optimizer (reference:
core/src/logical/LogicalPlan.cc — optimizeFilters/projection pushdown via
ColumnRewriteVisitor; csv.selectionPushdown option): we statically analyze
which source columns each UDF actually reads (dict-style subscripts with
constant keys) and prune everything else at the Arrow read — unread columns
are never parsed, decoded, or shipped to the device.
"""

from __future__ import annotations

import ast
from typing import Optional

from ..core import typesys as T
from . import logical as L

ALL = None  # sentinel: reads the whole row


def udf_read_columns(udf) -> Optional[set[str]]:
    """Column names a single-param UDF reads via x['col'] subscripts, or ALL
    if the row escapes (used whole, iterated, multi-param...)."""
    params = udf.params
    if len(params) != 1:
        return ALL
    p = params[0]
    if udf.source == "":
        return ALL
    reads = _param_subscript_reads(udf.tree, p)
    if reads is ALL:
        return ALL
    # any OTHER use of the param leaks the whole row
    leaks = _param_leaks(udf.tree, p)
    return ALL if leaks else reads


def _param_subscript_reads(tree: ast.AST, p: str):
    """Constant-string subscript reads of param `p` (`p['col']`), or ALL
    when any subscript of `p` has a non-const-str key. Shared by the
    single-param (udf_read_columns) and aggregate row-param
    (agg_required_columns) analyses."""
    reads: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Subscript) and \
                isinstance(node.value, ast.Name) and node.value.id == p:
            if isinstance(node.slice, ast.Constant) and \
                    isinstance(node.slice.value, str):
                reads.add(node.slice.value)
            else:
                return ALL
    return reads


def _param_leaks(tree: ast.AST, p: str) -> bool:
    """True if `p` is used anywhere except as `p['const']`."""
    class V(ast.NodeVisitor):
        def __init__(self):
            self.leak = False
            self.root = tree   # the UDF's own lambda/def binds p by design

        def visit_Subscript(self, node: ast.Subscript):
            if isinstance(node.value, ast.Name) and node.value.id == p and \
                    isinstance(node.slice, ast.Constant) and \
                    isinstance(node.slice.value, str):
                self.visit(node.slice)
                return  # safe use; don't descend into node.value
            self.generic_visit(node)

        def visit_Name(self, node: ast.Name):
            if node.id == p:
                self.leak = True

        def _nested_scope(self, node):
            # a nested lambda/def whose own parameter SHADOWS the row param
            # creates a new binding: subscripts inside it are not row reads,
            # but the walk in _param_subscript_reads can't tell them apart —
            # treat the whole UDF as reading the full row (ast.arg is not a
            # Name, so visit_Name alone never sees the shadowing)
            if node is self.root:
                self.generic_visit(node)
                return node
            from ..compiler.analyzer import _all_params

            if p in _all_params(node):
                self.leak = True
                return node
            self.generic_visit(node)
            return node

        def visit_Lambda(self, node: ast.Lambda):
            return self._nested_scope(node)

        def visit_FunctionDef(self, node):
            return self._nested_scope(node)

        def visit_AsyncFunctionDef(self, node):
            return self._nested_scope(node)

    v = V()
    v.visit(tree)
    return v.leak


def op_reads(op: L.LogicalOperator, current_columns) -> Optional[set[str]]:
    """Columns (by their CURRENT names) an operator reads."""
    if isinstance(op, L.MapColumnOperator):
        return {op.column}
    if isinstance(op, (L.MapOperator, L.FilterOperator,
                       L.WithColumnOperator)):
        return udf_read_columns(op.udf)
    if isinstance(op, L.ResolveOperator):
        return udf_read_columns(op.udf)
    if isinstance(op, L.SelectColumnsOperator):
        out = set()
        for c in op.selected:
            if isinstance(c, int):
                if current_columns is None or c >= len(current_columns):
                    return ALL
                out.add(current_columns[c])
            else:
                out.add(c)
        return out
    if isinstance(op, (L.RenameColumnOperator, L.IgnoreOperator,
                       L.TakeOperator, L.DecodeOperator)):
        return set()
    return ALL  # unknown operator: be safe


def agg_required_columns(agg_op) -> Optional[set[str]]:
    """Columns an aggregate breaker reads from its input stage's OUTPUT:
    key columns + the row-param subscripts of the aggregate UDF (the `x`
    in `lambda a, x: ...`). None = whole row (unique, leaking UDFs).
    Feeds projection pushdown across the stage boundary — tpch q1's
    lineitem tax/shipdate columns stop being decoded/staged."""
    from . import aggregates as A

    if not isinstance(agg_op, (A.AggregateOperator,
                               A.AggregateByKeyOperator)):
        return None
    udf = agg_op.aggregate_udf
    if udf.source == "" or len(udf.params) != 2:
        return None
    p = udf.params[1]
    if _param_leaks(udf.tree, p):
        return None
    reads = _param_subscript_reads(udf.tree, p)
    if reads is ALL:
        return None
    reads.update(getattr(agg_op, "key_columns", []) or [])
    return reads


def required_source_columns(source_columns: tuple[str, ...],
                            ops: list[L.LogicalOperator],
                            output_required: Optional[set] = None
                            ) -> Optional[list[str]]:
    """Minimal subset of source columns the chain needs, in source order;
    None if the whole row is required somewhere. `output_required` narrows
    the stage-output liveness to the columns a downstream breaker
    actually consumes (everything, when None)."""
    alias: dict[str, Optional[str]] = {c: c for c in source_columns}
    required: set[str] = set()
    cur_cols: Optional[list[str]] = list(source_columns)

    def add_reads(reads) -> bool:
        if reads is ALL:
            return False
        for r in reads:
            src = alias.get(r)
            if src:
                required.add(src)
        return True

    for i, op in enumerate(ops):
        reads = op_reads(op, cur_cols)
        if not add_reads(reads):
            return None
        if isinstance(op, L.MapOperator):
            # the map consumes the row — but its resolvers receive the
            # PRE-map row, so account for their reads before stopping
            j = i + 1
            while j < len(ops) and isinstance(
                    ops[j], (L.ResolveOperator, L.IgnoreOperator)):
                if not add_reads(op_reads(ops[j], cur_cols)):
                    return None
                j += 1
            return [c for c in source_columns if c in required]
        if isinstance(op, L.WithColumnOperator):
            alias[op.column] = None  # derived (or overwritten) column
            if cur_cols is not None and op.column not in cur_cols:
                cur_cols.append(op.column)
        elif isinstance(op, L.RenameColumnOperator):
            old = op.old if isinstance(op.old, str) else (
                cur_cols[op.old] if cur_cols else None)
            if old is None:
                return None
            alias[op.new] = alias.pop(old, None)
            if cur_cols is not None:
                cur_cols = [op.new if c == old else c for c in cur_cols]
        elif isinstance(op, L.SelectColumnsOperator):
            sel = []
            for c in op.selected:
                sel.append(cur_cols[c] if isinstance(c, int) and cur_cols
                           else c)
            alias = {c: alias.get(c) for c in sel}
            cur_cols = list(sel)
    # stage-output liveness: everything that survives — or, when the
    # downstream breaker declared its reads, just those columns
    if output_required is None:
        required |= {s for s in alias.values() if s}
    else:
        for name in output_required:
            src = alias.get(name)
            if src:
                required.add(src)
    return [c for c in source_columns if c in required]


def split_filter_conjunctions(ops: list) -> list:
    """FilterBreakdownVisitor analog (reference: FilterBreakdownVisitor.cc;
    LogicalPlan.cc emitPartialFilters): a filter whose body is `a and b`
    splits into SEQUENTIAL filters — order between the clauses is preserved
    (short-circuit intact relative to each other), but each clause can now
    hop over unrelated operators independently during pushdown."""
    out: list = []
    for i, op in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if isinstance(op, L.FilterOperator) and not isinstance(
                nxt, (L.ResolveOperator, L.IgnoreOperator)):
            parts = _split_filter(op)
            if parts is not None:
                out.extend(parts)
                continue
        out.append(op)
    return out


def _split_filter(op) -> Optional[list]:
    from ..utils.reflection import UDFSource

    udf = op.udf
    tree = udf.tree
    if udf.source == "" or len(udf.params) != 1:
        return None
    if isinstance(tree, ast.Lambda):
        body = tree.body
    elif isinstance(tree, ast.FunctionDef):
        # strip DOCSTRINGS only — a bare-call Expr has side effects that a
        # split would silently drop
        stmts = [s for s in tree.body
                 if not (isinstance(s, ast.Expr)
                         and isinstance(s.value, ast.Constant)
                         and isinstance(s.value.value, str))]
        if len(stmts) != 1 or not isinstance(stmts[0], ast.Return):
            return None
        body = stmts[0].value
    else:
        return None
    if not isinstance(body, ast.BoolOp) or not isinstance(body.op, ast.And):
        return None
    # walrus bindings can flow between clauses: splitting unbinds them
    if any(isinstance(n, ast.NamedExpr) for n in ast.walk(body)):
        return None
    p = udf.params[0]
    filters: list = []
    for k, clause in enumerate(body.values):
        try:
            src = f"lambda {p}: ({ast.unparse(clause)})"
            fn = eval(compile(src, f"<filter-split-{udf.name}>", "eval"),
                      dict(udf.globals))
            sub_tree = ast.parse(src, mode="eval").body
            fop = L.FilterOperator(op.parent, fn)
        except Exception:
            return None
        fop.udf = UDFSource(fn, src, sub_tree, dict(udf.globals),
                            f"{udf.name}#and{k}")
        filters.append(fop)
    return filters


def filter_pushdown(ops: list) -> list:
    """Move filters ahead of operators whose outputs they don't read
    (reference: LogicalPlan.cc optimizeFilters — pushing filters toward the
    source shrinks every downstream operator's working set).

    A filter hops over a preceding op when:
      * the op is a Map: never (row shape changes);
      * the op is a WithColumn/MapColumn: the filter doesn't read the
        written column;
      * the op is Rename/Select: names translate through;
    and neither op has resolvers attached (resolver semantics bind to
    operator order).
    """
    guarded: set[int] = set()
    for i, op in enumerate(ops):
        if isinstance(op, (L.ResolveOperator, L.IgnoreOperator)) and i > 0:
            guarded.add(id(ops[i - 1]))
            guarded.add(id(op))

    result = list(ops)
    changed = True
    while changed:
        changed = False
        for i in range(1, len(result)):
            f = result[i]
            prev = result[i - 1]
            if not isinstance(f, L.FilterOperator):
                continue
            if id(f) in guarded or id(prev) in guarded:
                continue
            reads = udf_read_columns(f.udf)
            if reads is ALL:
                continue
            if isinstance(prev, L.WithColumnOperator):
                if prev.column in reads:
                    continue
            elif isinstance(prev, L.MapColumnOperator):
                if prev.column in reads:
                    continue
            elif isinstance(prev, L.RenameColumnOperator):
                if prev.new in reads:
                    continue  # name doesn't exist before the rename
            else:
                continue  # Map/Select/Decode/aggregates: don't hop
            result[i - 1], result[i] = f, prev
            changed = True
    return result


def push_filters_through_joins(chain: list) -> list:
    """Push single-side filters ACROSS join boundaries (reference:
    FilterBreakdownVisitor.cc + LogicalPlan.cc optimizeFilters/
    emitPartialFilters — key-side predicates move through join build/probe
    sides so the join materializes fewer rows).

    `chain` is plan_stages' source→sink operator list. A filter downstream
    of a join pushes when every column it reads traces (through renames /
    untouched withColumn/mapColumn outputs) to ONE side of the join:

      * LEFT (probe) side — sound for inner AND left joins: the clone runs
        before the join in the same chain;
      * RIGHT (build) side — inner joins only (a left join keeps unmatched
        probe rows, so dropping build rows early changes nulls): the join
        node is shallow-copied with the clone spliced above its build
        parent (the user's DAG is never mutated; JoinStage plans the build
        side recursively from that parent).

    Column names rewrite via AST (x['CarrierName'] -> x['AirlineName'] ->
    undecorated side name). Resolvers/ignores between filter and join
    block the push (the filter must see resolved rows). Same
    exception-semantics caveat as in-stage pushdown, same option gate
    (tuplex.optimizer.filterPushdown)."""
    import copy

    from .joins import JoinOperator

    def attached_resolver(i: int) -> bool:
        nxt = chain[i + 1] if i + 1 < len(chain) else None
        return isinstance(nxt, (L.ResolveOperator, L.IgnoreOperator))

    changed = True
    while changed:
        changed = False
        for fi, f in enumerate(chain):
            if not isinstance(f, L.FilterOperator) or attached_resolver(fi):
                continue
            reads = udf_read_columns(f.udf)
            if reads is ALL or not reads:
                continue
            # walk upstream translating names until the nearest join
            mapping = {r: r for r in reads}     # filter name -> name at op k
            ji = None
            for k in range(fi - 1, -1, -1):
                op = chain[k]
                if isinstance(op, JoinOperator):
                    ji = k
                    break
                if isinstance(op, (L.ResolveOperator, L.IgnoreOperator)):
                    ji = None
                    break
                if isinstance(op, L.FilterOperator):
                    continue
                if isinstance(op, L.RenameColumnOperator):
                    if op.old in mapping.values():
                        ji = None   # upstream-only name already in use
                        break
                    mapping = {r: (op.old if n == op.new else n)
                               for r, n in mapping.items()}
                    continue
                if isinstance(op, (L.WithColumnOperator,
                                   L.MapColumnOperator)):
                    if op.column in mapping.values():
                        ji = None   # reads a column this op writes
                        break
                    continue
                if isinstance(op, L.SelectColumnsOperator):
                    sel = set(c for c in op.selected if isinstance(c, str))
                    if any(isinstance(c, int) for c in op.selected) or \
                            not set(mapping.values()) <= sel:
                        ji = None
                        break
                    continue
                ji = None           # Map / aggregate / unknown: stop
                break
            if ji is None:
                continue
            j = chain[ji]
            side_map = _classify_join_side(j, set(mapping.values()))
            if side_map is None:
                continue
            side, names = side_map
            if side == "right" and j.how != "inner":
                continue
            full_map = {r: names[n] for r, n in mapping.items()}
            parent = j.parents[0] if side == "left" else j.parents[1]
            clone = _rename_filter(f, full_map, parent)
            if clone is None:
                continue
            if side == "left":
                del chain[fi]
                chain.insert(ji, clone)
            else:
                j2 = copy.copy(j)
                j2.parents = [j.parents[0], clone]
                chain[ji] = j2
                del chain[fi]
            changed = True
            break
    return chain


def _classify_join_side(j, names: set):
    """Which join side ALL `names` (join-output columns) come from:
    ("left"|"right", {output name -> side-local name}) or None if mixed."""
    ls = j.left.schema()
    rs = j.right.schema()
    lk = ls.columns.index(j.left_column)
    rk = rs.columns.index(j.right_column)
    left_names = {j._decorate(c, 0): c
                  for i, c in enumerate(ls.columns) if i != lk}
    left_names[j.left_column] = j.left_column
    right_names = {j._decorate(c, 1): c
                   for i, c in enumerate(rs.columns) if i != rk}
    # the key column is both sides' key: usable on either
    right_key_alias = {j.left_column: j.right_column}
    if names <= set(left_names):
        return "left", left_names
    if names <= set(right_names) | set(right_key_alias):
        return "right", {**right_names, **right_key_alias}
    return None


def _rename_filter(f, mapping: dict, parent):
    """Clone a filter with its UDF's x['col'] subscripts renamed."""
    import copy

    from ..utils.reflection import UDFSource

    udf = f.udf
    if udf.source == "" or len(udf.params) != 1:
        return None
    p = udf.params[0]
    tree = copy.deepcopy(udf.tree)

    class R(ast.NodeTransformer):
        def visit_Subscript(self, node: ast.Subscript):
            self.generic_visit(node)
            if isinstance(node.value, ast.Name) and node.value.id == p and \
                    isinstance(node.slice, ast.Constant) and \
                    node.slice.value in mapping:
                node.slice = ast.Constant(mapping[node.slice.value])
            return node

    tree = ast.fix_missing_locations(R().visit(tree))
    try:
        if isinstance(tree, ast.Lambda):
            src = ast.unparse(tree)
            fn = eval(compile(src, f"<join-push-{udf.name}>", "eval"),
                      dict(udf.globals))
        elif isinstance(tree, ast.FunctionDef):
            src = ast.unparse(tree)
            ns = dict(udf.globals)
            exec(compile(src, f"<join-push-{udf.name}>", "exec"), ns)
            fn = ns[tree.name]
        else:
            return None
    except Exception:
        return None
    fop = L.FilterOperator(parent, fn)
    fop.udf = UDFSource(fn, src, tree, dict(udf.globals),
                        f"{udf.name}#joinpush")
    return fop


def reorder_filters(ops: list) -> list:
    """Operator reordering (reference: LogicalPlan.cc's
    tuplex.optimizer.operatorReordering, off by default there too): order
    CONSECUTIVE runs of filters by estimated selectivity so the most
    selective predicate runs first and shrinks the working set for the rest.

    Selectivity is estimated by running each filter's UDF over its
    operator's traced sample; rows that raise count as passing (they must
    still reach the filter that raises for exception parity). Like the
    reference, this is opt-in: reordering changes WHICH filter first drops
    (or raises on) a row, so per-operator exception attribution can shift.
    """
    result = list(ops)
    i = 0
    while i < len(result):
        if not isinstance(result[i], L.FilterOperator):
            i += 1
            continue
        j = i
        while j < len(result) and isinstance(result[j], L.FilterOperator):
            j += 1
        # resolvers bind to the preceding operator: a guarded run stays put
        if j < len(result) and isinstance(
                result[j], (L.ResolveOperator, L.IgnoreOperator)):
            i = j + 1
            continue
        if j - i > 1:
            run = result[i:j]
            run.sort(key=_filter_selectivity)
            result[i:j] = run
        i = j
    return result


def _filter_selectivity(op) -> float:
    """Estimated pass fraction of a filter over its traced sample (lower =
    more selective = runs earlier); 1.0 when no sample is available."""
    from .logical import apply_udf_python

    try:
        sample = op.parent.cached_sample()
    except Exception:
        return 1.0
    if not sample:
        return 1.0
    passed = 0
    for row in sample:
        try:
            if apply_udf_python(op.udf, row):
                passed += 1
        except Exception:
            passed += 1  # must reach this filter to raise: treat as pass
    return passed / len(sample)


# ---------------------------------------------------------------------------
# projection through joins (reference: csv.selectionPushdown crosses joins)
# ---------------------------------------------------------------------------

_MEMO_ATTRS = ("_sample_memo", "_chain_key_memo", "_branch_prof_memo",
               "_prof_ci", "sample_exceptions", "_sample_trace_skipped")


def relink(op: L.LogicalOperator, parents: list) -> L.LogicalOperator:
    """A shallow copy of `op` over other parents, with every memo that
    depends on its upstream chain cleared. The operator id survives the
    copy, so metrics and history attribution are unchanged."""
    import copy

    op = copy.copy(op)
    op.parents = list(parents)
    if hasattr(op, "_schema_cache"):
        op._schema_cache = None
    for a in _MEMO_ATTRS:
        op.__dict__.pop(a, None)
    return op


def _join_side_names(j, left_cols, right_cols) -> tuple:
    """({output name -> left column}, {output name -> right column}) of a
    join over sides with these columns; the key maps to both sides."""
    left = {(c if c == j.left_column else j._decorate(c, 0)): c
            for c in left_cols}
    right = {j._decorate(c, 1): c for c in right_cols
             if c != j.right_column}
    return left, right


def _join_columns(j, left_cols, right_cols) -> list:
    """Output column order of JoinOperator.schema(), from names alone."""
    return ([j._decorate(c, 0) for c in left_cols if c != j.left_column]
            + [j.left_column]
            + [j._decorate(c, 1) for c in right_cols if c != j.right_column])


def _columns_after(op, cur):
    """Column names after `op`, given the names `cur` before it (None when
    they cannot be told without the operator's own schema)."""
    if isinstance(op, L.RenameColumnOperator):
        old = op.old if isinstance(op.old, str) else cur[op.old]
        return [op.new if c == old else c for c in cur]
    if isinstance(op, L.WithColumnOperator):
        return cur if op.column in cur else cur + [op.column]
    if isinstance(op, L.SelectColumnsOperator):
        return [cur[c] if isinstance(c, int) else c for c in op.selected]
    if isinstance(op, (L.MapColumnOperator, L.FilterOperator,
                       L.ResolveOperator, L.IgnoreOperator,
                       L.DecodeOperator)):
        return cur
    cols = op.columns()         # map, aggregate: the operator's own names
    return list(cols) if cols else None


# string methods that take no argument and raise on no string
_TOTAL_STR_METHODS = frozenset({"lower", "upper", "strip", "lstrip", "rstrip",
                                "title", "capitalize", "casefold", "swapcase"})


def _total_when_guarded(op) -> bool:
    """Whether a `mapColumn` UDF provably raises on no value a string
    column can hold (a string or None): `f(x) if x else <constant>`, where
    `f(x)` is `x` under methods of `_TOTAL_STR_METHODS` called with no
    argument, or `string.capwords` of such. Deliberately narrow: it decides
    whether an operator whose result nothing reads may be dropped, and an
    operator that can raise drops its row (`x.format()` on "{0}" does, and
    `x.center("a")`: neither is on the list)."""
    if not isinstance(op, L.MapColumnOperator):
        return False
    udf, tree = op.udf, op.udf.tree
    if not isinstance(tree, ast.Lambda) or len(udf.params) != 1:
        return False
    ptype = op.parent.schema()
    t = ptype.types[ptype.columns.index(op.column)]
    if t.without_option() is not T.STR:
        return False
    p, body = udf.params[0], tree.body
    if not (isinstance(body, ast.IfExp) and isinstance(body.test, ast.Name)
            and body.test.id == p and isinstance(body.orelse, ast.Constant)):
        return False

    def total(e) -> bool:
        if isinstance(e, ast.Name):
            return e.id == p
        if not (isinstance(e, ast.Call) and not e.keywords
                and isinstance(e.func, ast.Attribute)):
            return False
        f = e.func
        if isinstance(f.value, ast.Name) and f.value.id != p:
            import string

            return (udf.globals.get(f.value.id) is string
                    and f.attr == "capwords" and len(e.args) == 1
                    and total(e.args[0]))
        return (f.attr in _TOTAL_STR_METHODS and not e.args
                and total(f.value))

    return total(body.body)


def project_through_joins(chain: list, source):
    """Projection through joins: one backward pass over `chain`
    (plan_stages' source→sink operator list) from the sink gives every
    point of the chain the columns its consumers read — before a join, the
    key plus what the operators after the join read of that side, through
    the prefixes and renames; for the build side the same. A forward pass
    then puts a `selectColumns` of the live columns before each join (and
    at the foot of its build side, whose sub-plan `JoinStage` plans from
    there), so that `required_source_columns` prunes the sources and no
    join carries a column that nothing reads, and drops a `mapColumn`
    whose result nothing reads and which provably cannot raise.

    Returns (chain, joins crossed); the chain is the one given unless
    something was pruned, and the user's DAG is never mutated."""
    from .joins import JoinOperator

    # ---- the column names before each operator, as the user wrote it ----
    names: list = []
    cur = list(source.columns() or ()) or None
    for op in chain:
        names.append(cur)
        if cur is None:                 # unnamed rows: nothing to prune by
            return chain, 0
        if isinstance(op, JoinOperator):
            cur = _join_columns(op, cur, list(op.right.columns() or ()))
        else:
            cur = _columns_after(op, cur)
    # ---- backward: the columns live after each operator (None: all) ----
    live: Optional[set] = None      # the sink's consumer reads every column
    keep: dict = {}        # position of a join -> (left, right) live columns
    drop: set = set()      # positions of dead operators that cannot raise
    carry: Optional[set] = set()   # reads of the resolvers of the operator
    for k in range(len(chain) - 1, -1, -1):             # before them
        op = chain[k]
        nxt = chain[k + 1] if k + 1 < len(chain) else None
        if isinstance(op, L.ResolveOperator):
            reads = udf_read_columns(op.udf)
            carry = ALL if reads is ALL or carry is ALL else carry | reads
            continue
        if isinstance(op, L.IgnoreOperator):
            continue
        if isinstance(op, JoinOperator):
            if live is not None:
                lnames, rnames = _join_side_names(
                    op, names[k], list(op.right.columns() or ()))
                keep[k] = (
                    {op.left_column} | {lnames[n] for n in live
                                        if n in lnames},
                    {op.right_column} | {rnames[n] for n in live
                                         if n in rnames})
                live = keep[k][0]
        elif live is not None and isinstance(op, L.MapColumnOperator) and \
                op.column not in live and not isinstance(
                    nxt, (L.ResolveOperator, L.IgnoreOperator)) and \
                _total_when_guarded(op):
            drop.add(k)
        else:
            live = _live_before(op, live, names[k])
        if carry is ALL:
            live = None
        elif carry and live is not None:
            live = live | carry
        carry = set()
    if not keep and not drop:
        return chain, 0
    # ---- forward: selects before the joins, dead operators dropped ----
    out: list = []
    prev = source
    cur = names[0]
    crossed = 0
    for k, op in enumerate(chain):
        if k in drop:
            continue
        if isinstance(op, JoinOperator):
            right = op.right
            rcols = list(right.columns() or ())
            if k in keep:
                lkeep, rkeep = keep[k]
                if len(lkeep) < len(cur) or len(rkeep) < len(rcols):
                    crossed += 1
                if len(lkeep) < len(cur):
                    cur = [c for c in cur if c in lkeep]
                    prev = L.SelectColumnsOperator(prev, cur)
                    out.append(prev)
                if len(rkeep) < len(rcols):
                    rcols = [c for c in rcols if c in rkeep]
                    right = L.SelectColumnsOperator(right, rcols)
            if prev is not op.left or right is not op.right:
                op = relink(op, [prev, right])
            cur = _join_columns(op, cur, rcols)
        else:
            if op.parent is not prev:
                if isinstance(op, L.SelectColumnsOperator) and any(
                        isinstance(c, int) for c in op.selected):
                    # positions shift under a pruned row: select by name
                    op = L.SelectColumnsOperator(prev, [
                        names[k][c] if isinstance(c, int) else c
                        for c in op.selected])
                else:
                    op = relink(op, [prev] + list(op.parents[1:]))
            cur = _columns_after(op, cur)
        out.append(op)
        prev = op
    return out, crossed


def _live_before(op, live: Optional[set], cols: list) -> Optional[set]:
    """The columns live before `op` (None: all), given those live after it
    and the column names before it. An operator runs whether or not its
    result is read, so its own reads are always live."""
    if isinstance(op, L.SelectColumnsOperator):
        names = op_reads(op, cols)
        if names is ALL or live is None:
            return names                         # ALL is None
        return (names & live) or names
    if isinstance(op, L.MapOperator):
        return udf_read_columns(op.udf)
    if op.is_breaker():
        return agg_required_columns(op)          # None: the whole row
    reads = op_reads(op, cols)
    if reads is ALL or live is None:
        return None
    if isinstance(op, L.RenameColumnOperator):
        if not isinstance(op.old, str):
            return None
        return {op.old if n == op.new else n for n in live}
    if isinstance(op, L.WithColumnOperator):
        return (live - {op.column}) | reads
    return live | reads
