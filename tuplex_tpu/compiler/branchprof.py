"""Sample-driven branch profiles for speculative if/else pruning.

The reference prunes UDF branches its row sample never takes and lets
violating rows fall to the general/interpreter ladder (reference:
codegen/src/RemoveDeadBranchesVisitor.cc:1-147, fed by TraceVisitor branch
annotations, core/include/TraceVisitor.h:25-80). The emitter here predicates
both arms of every if/else under boolean masks — correct, but every row pays
device compute for arms almost no row takes.

This module produces the evidence: it instruments a copy of the UDF's AST so
every `If`/`IfExp` test routes through a recorder, runs the instrumented
function over the operator's existing sample rows, and reports which arms the
sample observed. The emitter then emits ONLY the observed arm and raises
NORMALCASEVIOLATION for rows that would enter a cold arm (they resolve
exactly on the general tier / interpreter, like every other normal-case
violation).

Profiles are keyed by (node kind, lineno, col_offset) of the ORIGINAL
`udf.tree` nodes — the instrumented tree is a deepcopy, so locations match
without re-parsing (the tree may come from a larger enclosing parse whose
line numbers a re-parse of `udf.source` would not reproduce).
"""

from __future__ import annotations

import ast
import copy
from typing import Callable

_PROFILE_ROW_CAP = 1000
# An arm counts as observed where the sample took it in at least ARM_SHARE
# of the test's trials, and a test says anything only from MIN_TRIALS
# trials on: a single row decides nothing. Some 700-1,000 rows are sampled,
# so "never taken" and "taken once" are one draw apart for an event of one
# row in a thousand, and a plan that turned on that draw would turn, and
# compile anew, between two files of one distribution.
MIN_TRIALS = 100
ARM_SHARE = 0.01


def branch_key(node: ast.AST) -> tuple:
    return (type(node).__name__, node.lineno, node.col_offset)


class _WrapTests(ast.NodeTransformer):
    """Wrap every If/IfExp test in `__tpx_b__(<key index>, test)`."""

    def __init__(self):
        self.keys: list[tuple] = []
        self.worth: list[tuple] = []    # (then, else): worth pruning at all

    def _wrap(self, node):
        node = self.generic_visit(node)
        idx = len(self.keys)
        self.keys.append(branch_key(node))
        self.worth.append((arm_weight(node.body) >= 1,
                           bool(node.orelse)
                           and arm_weight(node.orelse) >= 1))
        call = ast.Call(func=ast.Name(id="__tpx_b__", ctx=ast.Load()),
                        args=[ast.Constant(value=idx), node.test],
                        keywords=[])
        ast.copy_location(call, node.test)
        node.test = call
        return node

    visit_If = _wrap
    visit_IfExp = _wrap


def _build_instrumented(udf) -> tuple[Callable, dict, "_WrapTests"]:
    tree = copy.deepcopy(udf.tree)
    w = _WrapTests()
    tree = w.visit(tree)
    ast.fix_missing_locations(tree)
    hits: dict[int, list[int]] = {}

    def rec(i, v):
        s = hits.setdefault(i, [0, 0])
        s[0 if v else 1] += 1
        return v

    g = dict(udf.globals)
    g["__tpx_b__"] = rec
    if isinstance(tree, ast.Lambda):
        expr = ast.Expression(body=tree)
        ast.fix_missing_locations(expr)
        f = eval(compile(expr, "<branchprof>", "eval"), g)
    else:
        mod = ast.Module(body=[tree], type_ignores=[])
        ast.fix_missing_locations(mod)
        exec(compile(mod, "<branchprof>", "exec"), g)
        f = g[tree.name]
    return f, hits, w


_CHEAP_CALLS = {"len", "abs", "min", "max", "ord", "chr", "bool"}


def arm_weight(arm) -> int:
    """Static cost estimate of a branch arm (stmt list or expr): method
    calls / casts are columnar kernels (string scans, parses), loops and
    comprehensions unroll — those make pruning pay. Pure assignments of
    cheap expressions cost nothing under predication, so pruning them only
    buys an error-lattice update."""
    stmts = arm if isinstance(arm, list) else [arm]
    w = 0
    for s in stmts:
        for n in ast.walk(s):
            if isinstance(n, ast.Call):
                f = n.func
                if isinstance(f, ast.Name) and f.id in _CHEAP_CALLS:
                    continue
                w += 1
            elif isinstance(n, (ast.For, ast.While, ast.ListComp,
                                ast.SetComp, ast.DictComp,
                                ast.GeneratorExp)):
                w += 3
    return w


def observed(n_true: int, n_false: int,
             worth: tuple = (True, True)) -> tuple[bool, bool]:
    """(saw_true, saw_false) of one test from its counts: both arms stand
    as observed below MIN_TRIALS trials (no evidence prunes either), an arm
    is observed from ARM_SHARE of the trials on, and an arm not `worth`
    pruning (`arm_weight`) is observed whatever the sample did, so that a
    profile says only what changes the emitted kernel."""
    trials = n_true + n_false
    if trials < MIN_TRIALS:
        return True, True
    need = max(1.0, ARM_SHARE * trials)
    return (n_true >= need or not worth[0], n_false >= need or not worth[1])


def profile_branches(udf, rows, call: Callable) -> dict:
    """{branch_key: (saw_true, saw_false)} from running the instrumented UDF
    over `rows` via `call(f, row)` (the operator's own calling convention);
    what counts as seen is `observed`'s to say, and a test with both arms
    seen is left out (it prunes nothing). Rows that raise contribute
    whatever branches they reached before the error — same evidence the
    reference's TraceVisitor collects. Returns {} when the UDF has no
    branches or cannot be instrumented (no pruning)."""
    if not rows:
        return {}
    if not any(isinstance(n, (ast.If, ast.IfExp))
               for n in ast.walk(udf.tree)):
        return {}
    try:
        f, hits, w = _build_instrumented(udf)
    except Exception:
        return {}
    for r in rows[:_PROFILE_ROW_CAP]:
        try:
            call(f, r)
        except Exception:
            pass
    prof = {w.keys[i]: observed(*v, w.worth[i]) for i, v in hits.items()}
    return {k: v for k, v in prof.items() if v != (True, True)}
