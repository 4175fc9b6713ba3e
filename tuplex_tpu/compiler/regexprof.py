"""Sample-driven widths of a UDF's regex capture groups.

A compiled `re.search` / `re.match` over a wide string column slices each
capture group into a column of its own (compiler/emitter.py); every later
pass over a group (parses, compares, the stage's outputs, the wire, the
host's matrices, joins) reads that column's width, not the subject's. This
module gives each group the width its sample needs: it re-runs the UDF
over the operator's sample rows with `re` recording the length of every
group each constant pattern captured, and sizes a group's window to the
power of two at or above the sample's 99th percentile of those lengths
(at least 8 bytes). A row whose group is longer than its window routes to
the interpreter (fail-safe, never truncated). The 99th percentile, not the
widest, so that one long line of a sample cannot widen every row's column
or turn the plan on the draw; the power of two, so that the window does
not move with a percentile that sits inside an octave.
"""

from __future__ import annotations

import ast
import re
import types
from typing import Callable

from .branchprof import MIN_TRIALS, _PROFILE_ROW_CAP, compile_tree

PERCENTILE = 99
MIN_WINDOW = 8


class _Recording:
    """`re` for the profile run: `search` and `match` note the lengths of
    the groups they captured, keyed by the pattern the emitter compiles
    (`match` anchors it); everything else is the module's own."""

    def __init__(self):
        self.lens: dict[str, list] = {}

    def __getattr__(self, name):
        return getattr(re, name)

    def _note(self, pattern, m, flags):
        if m is not None and isinstance(pattern, str) and not flags:
            self.lens.setdefault(pattern, []).append(
                [len(g or "") for g in m.groups()])
        return m

    def search(self, pattern, string, flags=0):
        return self._note(pattern, re.search(pattern, string, flags), flags)

    def match(self, pattern, string, flags=0):
        m = re.match(pattern, string, flags)
        if isinstance(pattern, str) and not pattern.startswith("^"):
            pattern = "^" + pattern
        return self._note(pattern, m, flags)


def window(lengths: list) -> int:
    """The window of one group from its sampled lengths: the power of two
    at or above their nearest-rank PERCENTILE, at least MIN_WINDOW."""
    s = sorted(lengths)
    at = s[-(-len(s) * PERCENTILE // 100) - 1]
    return max(MIN_WINDOW, 1 << (at - 1).bit_length())


def _shim(names, globals_: dict, rec: _Recording, depth: int = 2):
    """A copy of `globals_` in which the `re` module, under any of `names`,
    is `rec`, and so is it in the functions under `names` that the UDF
    calls (ParseWithRegex behind `lambda x: ParseWithRegex(x[...])`), to
    `depth` calls deep; None where no name reaches `re`."""
    g, hit = dict(globals_), False
    for k in names:
        v = g.get(k)
        if v is re:
            g[k], hit = rec, True
        elif depth and isinstance(v, types.FunctionType):
            inner = _shim(v.__code__.co_names, v.__globals__, rec, depth - 1)
            if inner is not None:
                g[k], hit = types.FunctionType(
                    v.__code__, inner, v.__name__, v.__defaults__,
                    v.__closure__), True
    return g if hit else None


def reaches_re(udf) -> bool:
    """Whether the UDF, or a function it calls, names the `re` module: only
    then is its sample worth a profile run."""
    return _shim(_names(udf), udf.globals, _Recording()) is not None


def _names(udf) -> set:
    return {n.id for n in ast.walk(udf.tree) if isinstance(n, ast.Name)}


def profile_group_widths(udf, rows, call: Callable) -> dict:
    """{pattern: (window of group 1, ..., of group n)} from running the UDF
    over `rows` via `call(f, row)` with `re` recording; a pattern matched on
    fewer than branchprof.MIN_TRIALS rows says nothing and is left out.
    {} where the UDF reaches no `re` module or cannot be re-run."""
    if not rows:
        return {}
    rec = _Recording()
    g = _shim(_names(udf), udf.globals, rec)
    if g is None:
        return {}
    try:
        f = compile_tree(udf.tree, g)
    except Exception:
        return {}
    for r in rows[:_PROFILE_ROW_CAP]:
        try:
            call(f, r)
        except Exception:
            pass
    return {p: tuple(window(col) for col in zip(*seen))
            for p, seen in rec.lens.items()
            if len(seen) >= MIN_TRIALS and seen[0]}
