"""Vectorized regex matching over byte-matrix columns.

The reference codegens re.search/re.sub into the compiled pipeline
(reference: codegen/include/FunctionRegistry.h:71-205;
StandardModules.cc:30-129 types the `re` module). The TPU equivalent here
compiles an ANCHORED regex subset into a sequence of whole-column kernel
steps over [N, W] byte matrices:

  * literals, char classes (\\d \\s \\w, [..] sets/ranges/negation, '.')
  * greedy quantifiers ? * + {m} {m,n}
  * capturing groups, ^ and $ anchors

Backtracking policy: one retreat level. When a greedy class run collides
with a following single-char literal (e.g. `(\\S*)\\s*"` where '"' is itself
non-space), the matcher retreats to the LAST literal occurrence inside the
run — exactly the first position python's backtracking would try. Rows where
the remaining pattern still fails are reported unmatched, and the caller
routes them to the interpreter: the compiled path therefore never SUCCEEDS
with a different answer than CPython, it can only fail-safe. Patterns
outside the subset raise NotCompilable (whole UDF interprets).
"""

from __future__ import annotations

import re as _pyre
from dataclasses import dataclass, field
from typing import Optional

from ..core.errors import NotCompilable
from ..runtime.jaxcfg import jnp

try:
    from re import _parser as _sre
    from re import _constants as _sc
except ImportError:  # pragma: no cover - older layout
    import sre_parse as _sre            # type: ignore
    import sre_constants as _sc         # type: ignore

_MAXREPEAT = _sc.MAXREPEAT


# ---------------------------------------------------------------------------
# pattern -> step list
# ---------------------------------------------------------------------------

@dataclass
class _Step:
    kind: str                    # "lit" | "class" | "open" | "close" | "end"
    spec: tuple = ()             # class spec items
    min: int = 1
    max: Optional[int] = 1      # None = unbounded
    group: int = -1
    # retreat plan (set on single-char lit steps during analysis)
    retreat_from: int = -1       # index of the greedy step to retreat into
    retreat_min: int = 0         # the greedy step's min (can't retreat past)
    retreat_groups: tuple = ()   # group ids whose END moves with the retreat


def _category_spec(cat) -> tuple:
    name = str(cat).rsplit("_", 1)[-1].lower()
    table = {
        "digit": (("range", 48, 57),),
        "space": (("lit", 9), ("lit", 10), ("lit", 11), ("lit", 12),
                  ("lit", 13), ("lit", 32)),
        "word": (("range", 48, 57), ("range", 65, 90), ("range", 97, 122),
                 ("lit", 95)),
    }
    neg = "not_" in str(cat).lower()
    base = table.get(name)
    if base is None:
        raise NotCompilable(f"regex category {cat}")
    return ((("neg",),) if neg else ()) + base


def _in_spec(items) -> tuple:
    spec: list = []
    neg = False
    for op, av in items:
        opn = str(op)
        if opn.endswith("NEGATE"):
            neg = True
        elif opn.endswith("LITERAL"):
            spec.append(("lit", av))
        elif opn.endswith("RANGE"):
            spec.append(("range", av[0], av[1]))
        elif opn.endswith("CATEGORY"):
            sub = _category_spec(av)
            if sub and sub[0] == ("neg",):
                # negated category inside a set: only as the whole set
                if len(items) != 1:
                    raise NotCompilable("negated category in mixed set")
                return sub
            spec.extend(sub)
        else:
            raise NotCompilable(f"regex set item {op}")
    return (("neg",),) + tuple(spec) if neg else tuple(spec)


def _flatten(tree, steps: list) -> None:
    for op, av in tree:
        opn = str(op)
        if opn.endswith("NOT_LITERAL"):
            # NOT_LITERAL must match before the LITERAL suffix check
            steps.append(_Step("class", (("neg",), ("lit", av))))
        elif opn.endswith("LITERAL"):
            steps.append(_Step("lit", (("lit", av),)))
        elif opn.endswith("ANY"):
            steps.append(_Step("class", (("neg",), ("lit", 10))))  # '.'
        elif opn.endswith("IN"):
            steps.append(_Step("class", _in_spec(av)))
        elif opn.endswith("MAX_REPEAT"):
            mn, mx, item = av
            if len(item) != 1:
                raise NotCompilable("regex repeat of a sequence")
            iop, iav = item[0]
            iopn = str(iop)
            if iopn.endswith("NOT_LITERAL"):
                spec = (("neg",), ("lit", iav))
            elif iopn.endswith("LITERAL"):
                spec = (("lit", iav),)
            elif iopn.endswith("IN"):
                spec = _in_spec(iav)
            elif iopn.endswith("ANY"):
                spec = (("neg",), ("lit", 10))
            else:
                raise NotCompilable(f"regex repeat of {iop}")
            steps.append(_Step("class", spec, min=mn,
                               max=None if mx == _MAXREPEAT else mx))
        elif opn.endswith("SUBPATTERN"):
            g, addf, delf, sub = av
            if addf or delf:
                raise NotCompilable("regex inline flags")
            steps.append(_Step("open", group=g))
            _flatten(sub, steps)
            steps.append(_Step("close", group=g))
        elif opn.endswith("AT"):
            name = str(av)
            if name.endswith("AT_BEGINNING"):
                if any(s.kind not in ("open",) for s in steps):
                    raise NotCompilable("^ not at pattern start")
            elif name.endswith("AT_END"):
                steps.append(_Step("end"))
            else:
                raise NotCompilable(f"regex anchor {av}")
        else:
            raise NotCompilable(f"regex op {op}")


def _byte_in_spec(byte: int, spec: tuple) -> bool:
    neg = bool(spec) and spec[0] == ("neg",)
    items = spec[1:] if neg else spec
    hit = any((it[0] == "lit" and byte == it[1]) or
              (it[0] == "range" and it[1] <= byte <= it[2]) for it in items)
    return hit != neg


def _byte_set(spec: tuple) -> frozenset:
    return frozenset(c for c in range(256) if _byte_in_spec(c, spec))


def _suspect_threshold(steps: list) -> int:
    """First step index at/after which a death may have unexplored
    backtracking alternatives (see CompiledRegex.__init__). Walks each
    variable-length run's reachable followers (skipping groups and min-0
    disjoint classes) looking for class overlap."""
    soft = len(steps)
    for j, stj in enumerate(steps):
        if stj.kind != "class" or \
                (stj.max is not None and stj.min == stj.max):
            continue    # fixed-width or non-run: no alternatives
        bs = _byte_set(stj.spec)
        k = j + 1
        while k < len(steps):
            sk = steps[k]
            if sk.kind in ("open", "close"):
                k += 1
                continue
            if sk.kind == "end":
                break                       # '$' consumes nothing
            if bs & _byte_set(sk.spec):
                if sk.retreat_from == j:
                    # one retreat level is exact; deeper lit occurrences
                    # are not — deaths from the lit onward are suspect
                    soft = min(soft, k)
                else:
                    soft = min(soft, j)
                break
            if sk.min >= 1:
                break   # must consume a char the run can't supply: rigid
            k += 1      # min-0 disjoint class can be empty: keep walking
    return soft


def _analyze_retreats(steps: list) -> None:
    """Mark single-char literal steps that can retreat into a preceding
    unbounded greedy class run (see module docstring for the exactness
    argument)."""
    for i, st in enumerate(steps):
        if st.kind != "lit" and not (st.kind == "class" and st.min == 1
                                     and st.max == 1
                                     and len(st.spec) == 1
                                     and st.spec[0][0] == "lit"):
            continue
        lit_byte = st.spec[-1][1] if st.spec[-1][0] == "lit" else None
        if lit_byte is None:
            continue
        groups: list = []
        j = i - 1
        while j >= 0:
            pj = steps[j]
            if pj.kind in ("open", "close"):
                if pj.kind == "close":
                    groups.append(pj.group)
                j -= 1
                continue
            if pj.kind == "class" and pj.min == 0 and \
                    not _byte_in_spec(lit_byte, pj.spec):
                j -= 1          # zero-width-able class disjoint from lit
                continue
            break
        if j >= 0 and steps[j].kind == "class" and steps[j].max is None \
                and _byte_in_spec(lit_byte, steps[j].spec):
            st.retreat_from = j
            st.retreat_min = steps[j].min
            st.retreat_groups = tuple(groups)


# ---------------------------------------------------------------------------
# runtime
# ---------------------------------------------------------------------------

def _class_mask(bytes_, spec: tuple):
    neg = bool(spec) and spec[0] == ("neg",)
    items = spec[1:] if neg else spec
    m = jnp.zeros(bytes_.shape, dtype=bool)
    for it in items:
        if it[0] == "lit":
            m = m | (bytes_ == it[1])
        else:
            m = m | ((bytes_ >= it[1]) & (bytes_ <= it[2]))
    return ~m if neg else m


class CompiledRegex:
    """Anchored matcher: match(bytes [N,W], lens) -> (matched [N],
    group_start [N, G+1], group_end [N, G+1]). Group 0 is the whole match."""

    def __init__(self, pattern: str):
        self.pattern = pattern
        try:
            tree = _sre.parse(pattern)
        except Exception as e:
            raise NotCompilable(f"regex parse: {e}")
        if tree.state.flags & ~(_pyre.UNICODE.value):
            raise NotCompilable("regex flags")
        steps: list[_Step] = []
        _flatten(list(tree), steps)
        if not pattern.startswith("^"):
            raise NotCompilable("only anchored (^) regex compiles")
        _analyze_retreats(steps)
        self.steps = steps
        self.n_groups = tree.state.groups - 1
        # fail-safety threshold: a greedy class-run whose reachable
        # follower is DISJOINT from its class is RIGID — no shorter run can
        # satisfy the follower (the boundary char stays in the run's
        # class), so deaths behind it are authoritative no-matches, not
        # suspects. Only runs with an OVERLAPPING follower admit deeper
        # backtracking: with single-char lit overlap the retreat explores
        # exactly python's first alternative (deaths at/after that lit are
        # suspect); other overlaps are unexplored (suspect from the run
        # itself). The logs headline's 3% malformed lines die at rigid
        # boundaries and now stay on device instead of routing (r4).
        self.first_var = _suspect_threshold(steps)

    def match(self, bytes_, lens):
        n, w = bytes_.shape
        pos = jnp.zeros(n, dtype=jnp.int32)
        alive = jnp.ones(n, dtype=bool)
        ng = self.n_groups
        gs = [jnp.zeros(n, dtype=jnp.int32) for _ in range(ng + 1)]
        ge = [jnp.zeros(n, dtype=jnp.int32) for _ in range(ng + 1)]
        positions = jnp.arange(w, dtype=jnp.int32)[None, :]
        greedy_state: dict[int, tuple] = {}   # step idx -> (start_pos)
        died_late = jnp.zeros(n, dtype=bool)  # failed at/after first_var

        def byte_at(p):
            idx = jnp.clip(p, 0, w - 1)
            return jnp.take_along_axis(bytes_, idx[:, None], 1)[:, 0]

        def note_deaths(si, before, after):
            # deaths BEFORE the suspect threshold are authoritative (every
            # earlier run is rigid); at/after it, unexplored backtracking
            # may exist
            nonlocal died_late
            if si >= self.first_var:
                died_late = died_late | (before & ~after)
            return after

        for si, st in enumerate(self.steps):
            if st.kind == "open":
                gs[st.group] = pos
                continue
            if st.kind == "close":
                ge[st.group] = pos
                continue
            if st.kind == "end":
                # python's $ also matches just before a trailing '\n'
                at_end = (pos == lens) | \
                    ((pos == lens - 1) & (byte_at(pos) == 10))
                alive = note_deaths(si, alive, alive & at_end)
                continue
            if st.kind == "lit" or (st.min == 1 and st.max == 1):
                inb = pos < lens
                ok = inb & _class_mask(byte_at(pos)[:, None],
                                       st.spec)[:, 0]
                if st.retreat_from >= 0:
                    # retreat into the greedy run: last lit occurrence
                    # (single max-reduce; hit == last >= 0 spares the any())
                    start = greedy_state[st.retreat_from]
                    lit = st.spec[-1][1]
                    window = (positions >=
                              (start + st.retreat_min)[:, None]) & \
                        (positions < pos[:, None]) & (bytes_ == lit)
                    last = jnp.max(jnp.where(window, positions, -1), axis=1)
                    hit = last >= 0
                    use = alive & ~ok & hit
                    # group ends recorded at the greedy end move back too
                    for g in st.retreat_groups:
                        ge[g] = jnp.where(use, last, ge[g])
                    pos = jnp.where(use, last, pos)
                    ok = ok | use
                alive = note_deaths(si, alive, alive & ok)
                pos = jnp.where(alive, pos + 1, pos)
                continue
            # greedy class run
            cmask = _class_mask(bytes_, st.spec)
            blocked = (~cmask) | (positions >= lens[:, None])
            beyond = blocked & (positions >= pos[:, None])
            first_stop = jnp.min(
                jnp.where(beyond, positions, w), axis=1)
            runlen = first_stop - pos
            if st.max is not None:
                runlen = jnp.minimum(runlen, st.max)
            alive = note_deaths(si, alive, alive & (runlen >= st.min))
            greedy_state[si] = pos
            pos = jnp.where(alive, pos + runlen, pos)
        ge[0] = pos
        suspect = died_late
        return alive, suspect, gs, ge

    # suspect rows the exact engine examines per batch (a share of the
    # batch, at least _DECIDE_MIN), and the width it scans them at: a
    # longer row, or one beyond the share, stays suspect
    _DECIDE_SHARE = 16
    _DECIDE_MIN = 64
    _DECIDE_W = 256

    @property
    def nfa(self):
        """The pattern's exact NFA (ops/nfa.py), or None where the NFA
        cannot hold it (then no suspect row is decided)."""
        if not hasattr(self, "_nfa"):
            from .nfa import compile_nfa

            try:
                self._nfa = compile_nfa(self.pattern)
            except NotCompilable:
                self._nfa = None
        return self._nfa

    def engine(self) -> str:
        """The engines a site on this pattern runs: the direct lowering,
        and the NFA implementation that decides its suspect rows."""
        if self.nfa is None or self.first_var >= len(self.steps):
            return "direct"
        from .nfa import _nfa_impl

        return f"direct+nfa:{_nfa_impl()}"

    def match_decided(self, bytes_, lens):
        """match(), with the suspect rows decided where that is exact: a
        suspect row is one whose run died where unexplored backtracking
        may remain, and the NFA answers exactly whether ANY match exists.
        A suspect row it proves unmatched is an authoritative no-match
        (python's re.search returns None there); one it proves matched
        stays suspect, since its groups are unknown. The suspects are
        gathered into a block of at most max(_DECIDE_MIN, N/_DECIDE_SHARE)
        rows scanned at _DECIDE_W bytes, so the scan costs a small share
        of the batch; suspects beyond the block or longer than the width
        stay suspect (fail-safe)."""
        alive, suspect, gs, ge = self.match(bytes_, lens)
        nfa = self.nfa
        if nfa is None or self.first_var >= len(self.steps):
            return alive, suspect, gs, ge
        n, w = bytes_.shape
        k = min(n, max(self._DECIDE_MIN, n // self._DECIDE_SHARE))
        dw = min(w, self._DECIDE_W)
        cand = suspect & (lens <= dw)
        idx = jnp.nonzero(cand, size=k, fill_value=n)[0]
        real = idx < n
        at = jnp.minimum(idx, n - 1)
        sub_l = jnp.where(real, jnp.take(lens, at), 0)
        found = nfa.match(jnp.take(bytes_[:, :dw], at, axis=0), sub_l)
        unmatched = jnp.zeros(n, dtype=bool).at[idx].set(
            real & ~found, mode="drop")
        return alive, suspect & ~unmatched, gs, ge


_REGEX_CACHE: dict[str, CompiledRegex] = {}


def compile_regex(pattern: str) -> CompiledRegex:
    rx = _REGEX_CACHE.get(pattern)
    if rx is None:
        rx = CompiledRegex(pattern)
        if len(_REGEX_CACHE) > 256:
            _REGEX_CACHE.clear()
        _REGEX_CACHE[pattern] = rx
    return rx
