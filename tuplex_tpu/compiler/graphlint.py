"""Jaxpr-plane static analysis: pre-submission compile-hazard vetting.

Both existing static passes stop at the Python layer (analyzer.py lints
UDF ASTs, typeinfer.py runs abstract types); the compile plane hands
every stage jaxpr to XLA blind, so pathological graphs are only
*survived* — 300 s deadline, SIGKILL, whole-stage tier degrade — never
predicted or avoided. This pass closes that gap: a cheap walk over a
stage's ClosedJaxpr (post-trace, pre-``lowered.compile()``) producing a
:class:`GraphReport` with

* an eqn census by primitive family,
* a static intermediate-buffer peak estimate from eqn avals (a sound
  upper bound on simultaneously-live temporaries, checked against the
  MemoryManager budget at plan time, before HBM ever sees the stage),
* dtype-creep (8-byte intermediates dominating a graph traced from
  32-bit inputs) and implicit-broadcast blowup findings,
* scatter/gather/one-hot/concat **compaction-chain** detection, and
* a weighted hazard score (predicted XLA:CPU compile seconds) with
  per-construct weights calibrated against measured compile times,
  broken down by primitive family instead of op count alone.

The load-bearing output is the ``wedge``-severity rule. Round 17
bisected the flights airport build-side stage (3 ops / 2.2k eqns,
>20 min / >120 GB on XLA:CPU — ROADMAP residue (c)) eqn-span by
eqn-span under the fork-isolated compiler:

* every prefix that leaves the assembled row buffers as computation
  ROOTS compiles in < 2 s;
* adding ANY post-assembly consumer of the wide row state — the
  terminal 26..28-operand ``optimization_barrier`` *or* the two-eqn
  row-valid epilogue — wedges the compile (kill at 45-120 s, > 20 min
  unattended);
* the trigger survives removing every scatter (a gather-based
  ``_scatter_cols`` rewrite still wedges), removing the terminal
  barrier alone, and splitting the wide barrier into per-leaf barriers,
  so no single eqn is at fault: XLA:CPU's fusion/emission pass goes
  superlinear on the *combination* of a dense string-compaction graph
  and a wide multi-string-column row materialization.

Measured over every stage of the five bundled pipelines (zillow,
flights, tpch, nyc311, logs — both the plan-time probe-shape trace and
the jaxprs the compile plane actually submits in production runs,
ground-truthed against forked deadline-killed XLA:CPU compiles),
exactly one structural signature separates the wedging stages from the
clean ones:

    eqns/op >= 300  AND  scatter+cumsum >= 10  AND  str row buffers >= 4

Two stages carry it, and both are measured wedges: the airport build
side (961 eqns/op, 12 compaction eqns, 7 str buffers) and the flights
probe-side mega-segment (394 eqns/op, 30 cumsum eqns, 5 str buffers —
its production compile blows even a 300 s deadline). Every clean stage
misses at least one axis with margin: the densest clean stages
(logs_strip at 1140 eqns/op, logs_regex at 1060) have ZERO compaction
eqns; the most compaction-heavy high-density clean stages (tpch q1/q6/
q19 at 6 compaction eqns) sit 40 % under the compaction floor with at
most 3 str buffers; the most compaction-heavy stage overall, flights[1]
with scatter=4 cumsum=4, sits at 77 eqns/op — 4x under the density
floor. That conjunction is pinned as rule ``wide-str-compaction`` and
test-enforced as both a zero-false-positive gate over all five
pipelines and a fires-on-airport regression.

Disabled (``TUPLEX_GRAPHLINT=0`` env kill switch, mirroring
devprof/excprof) every hook is one module-flag check — no trace, no
walk, no allocation.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field, replace
from typing import Optional

# ---------------------------------------------------------------------------
# enable gate (mirrors runtime/devprof: process-wide, env kill switch wins)
# ---------------------------------------------------------------------------


def _env_disabled() -> bool:
    return os.environ.get("TUPLEX_GRAPHLINT", "").strip().lower() \
        in ("0", "false", "off")


_enabled = not _env_disabled()


def enabled() -> bool:
    return _enabled


def enable(on: bool = True) -> None:
    """Process-wide gate. TUPLEX_GRAPHLINT=0 wins over any option-driven
    enable (A/B overhead timing, pathological-graph archaeology)."""
    global _enabled
    _enabled = bool(on) and not _env_disabled()


# hazard-score veto threshold (predicted compile seconds). 60 s sits
# a 2.6x margin above the worst CLEAN bundled stage (zillow[0] at
# 22.9 s) — by default only a wedge-severity finding (score forced to
# 1e9) crosses it, so vetting changes nothing on healthy plans.
_DEFAULT_THRESHOLD = 60.0
_threshold = _DEFAULT_THRESHOLD


def hazard_threshold() -> float:
    return _threshold


def set_hazard_threshold(value: float) -> None:
    """<= 0 disables the score veto (wedge findings still veto)."""
    global _threshold
    _threshold = float(value)


def apply_options(options) -> None:
    """Wire the process gate from ContextOptions. Like devprof, the
    ``tuplex.tpu.graphlint`` option turns vetting ON, never off — the
    gate is process-wide and another live Context may depend on it."""
    if options.get_bool("tuplex.tpu.graphlint", True):
        enable(True)
    set_hazard_threshold(options.get_float(
        "tuplex.tpu.hazardThreshold", _DEFAULT_THRESHOLD))


# ---------------------------------------------------------------------------
# primitive families + calibrated per-family compile-cost weights
# ---------------------------------------------------------------------------

# family -> estimated XLA:CPU compile seconds PER EQN. Calibrated by
# least-squares over the round-17 stage corpus (19 stages, forked
# compiles, probe shapes): clean stages run ~1.5-2.5 ms/eqn flat, with
# gather/sort/scatter/while carrying the residual above the flat rate.
# They are intentionally conservative — the score exists to rank and to
# veto, not to schedule.
FAMILY_WEIGHTS = {
    "scatter": 0.060,
    "gather": 0.012,
    "cumsum": 0.020,
    "sort": 0.050,
    "while": 0.080,
    "concat": 0.010,
    "onehot": 0.008,       # iota/eq one-hot expansions
    "broadcast": 0.003,
    "reduce": 0.004,
    "convert": 0.002,
    "control": 0.006,      # pjit/cond/custom-call bodies
    "elementwise": 0.0015,
}

_FAMILY_OF = {
    "scatter": "scatter", "scatter-add": "scatter",
    "gather": "gather", "dynamic_slice": "gather",
    "dynamic_update_slice": "scatter", "take_along_axis": "gather",
    "cumsum": "cumsum", "cumlogsumexp": "cumsum", "cummax": "cumsum",
    "cummin": "cumsum", "cumprod": "cumsum",
    "sort": "sort",
    "while": "while", "scan": "while",
    "concatenate": "concat", "pad": "concat",
    "iota": "onehot",
    "broadcast_in_dim": "broadcast", "reshape": "broadcast",
    "squeeze": "broadcast", "rev": "broadcast", "transpose": "broadcast",
    "convert_element_type": "convert", "bitcast_convert_type": "convert",
    "pjit": "control", "cond": "control", "custom_jvp_call": "control",
    "custom_vjp_call": "control", "remat": "control",
    "optimization_barrier": "control", "custom_call": "control",
}
for _p in ("reduce_sum", "reduce_max", "reduce_min", "reduce_and",
           "reduce_or", "reduce_prod", "argmax", "argmin",
           "reduce_precision"):
    _FAMILY_OF[_p] = "reduce"


def family_of(prim_name: str) -> str:
    return _FAMILY_OF.get(prim_name, "elementwise")


# wide-str-compaction thresholds (see module docstring for the corpus
# margins backing each number)
WEDGE_MIN_EQNS_PER_OP = 300
WEDGE_MIN_COMPACTION = 10      # scatter + cumsum eqns
WEDGE_MIN_STR_BUFS = 4         # >=2-d uint8 leaves in the row state

# dtype-creep / broadcast-blowup thresholds
_CREEP_MIN_COUNT = 50          # 8-byte-valued eqns before we bother
_CREEP_MIN_FRACTION = 0.25
_BLOWUP_RATIO = 64             # out.size / max(in.size) per broadcast
_BLOWUP_MIN_COUNT = 4


# ---------------------------------------------------------------------------
# report types
# ---------------------------------------------------------------------------


@dataclass
class Finding:
    """One named rule hit. ``severity``: info < warn < wedge. A wedge
    finding means "statically known to stall this platform's compiler"
    and forces the hazard score past any threshold."""

    rule: str
    severity: str
    message: str
    eqn_span: Optional[tuple] = None   # (first, last) top-level eqn idx

    def line(self) -> str:
        span = (f" [eqns {self.eqn_span[0]}..{self.eqn_span[1]}]"
                if self.eqn_span else "")
        return f"[{self.severity}] {self.rule}: {self.message}{span}"


@dataclass
class GraphReport:
    """Static analysis of one stage jaxpr (see module docstring)."""

    n_eqns: int = 0
    n_ops: int = 1
    census: dict = field(default_factory=dict)     # primitive -> count
    families: dict = field(default_factory=dict)   # family -> count
    peak_bytes: int = 0            # static live-set peak at traced shapes
    peak_fixed_bytes: int = 0      # peak share that does NOT scale w/ rows
    peak_row_bytes: int = 0        # peak share per traced row (scales)
    input_row_bytes: int = 0       # bytes per row across the INPUT avals
    traced_rows: int = 0           # leading batch dim of the traced avals
    str_bufs: int = 0              # >=2-d uint8 buffers in the outvars
    hazard_score: float = 0.0      # predicted compile seconds (see WEIGHTS)
    findings: list = field(default_factory=list)
    elapsed_ms: float = 0.0

    @property
    def wedge(self) -> bool:
        return any(f.severity == "wedge" for f in self.findings)

    def worst_severity(self) -> str:
        rank = {"info": 0, "warn": 1, "wedge": 2}
        worst = ""
        for f in self.findings:
            if not worst or rank.get(f.severity, 0) > rank.get(worst, 0):
                worst = f.severity
        return worst

    def peak_bytes_at(self, rows: int) -> int:
        """Scale the static peak to a target batch-row count. Sound as
        long as only leading-batch-dim buffers grow with rows (true for
        the columnar layout: every [B]/[B, W] leaf scales, consts and
        scalars don't)."""
        if self.traced_rows <= 0:
            return self.peak_bytes
        return self.peak_fixed_bytes + self.peak_row_bytes * max(rows, 0)

    def op_costs(self) -> list:
        """Per-op hazard costs for plan_split's split-point placement:
        the census-weighted cost spread uniformly over the stage's ops
        (the jaxpr does not delimit op boundaries, so the spread is the
        least-surprising sound choice; a wedge finding concentrates its
        weight instead so the split isolates SOMETHING rather than
        nothing)."""
        n = max(self.n_ops, 1)
        per = self.hazard_score / n
        return [per] * n

    def lines(self) -> list:
        """Human-readable summary block (lint / explain / compilestats)."""
        fams = ", ".join(f"{k}={v}" for k, v in sorted(
            self.families.items(), key=lambda kv: -kv[1]) if v)
        out = [
            f"eqns={self.n_eqns} ops={self.n_ops} "
            f"hazard={self.hazard_score:.2f}s peak={self.peak_bytes}B "
            f"(+{self.peak_row_bytes}B/row)",
            f"families: {fams}" if fams else "families: (empty)",
        ]
        out.extend(f.line() for f in self.findings)
        return out


# ---------------------------------------------------------------------------
# the analysis pass
# ---------------------------------------------------------------------------


def _aval_nbytes(aval) -> int:
    try:
        size = 1
        for d in aval.shape:
            size *= int(d)
        return size * aval.dtype.itemsize
    except Exception:
        return 0


def _walk_census(jaxpr, census: dict) -> int:
    """Full census including nested jaxprs (pjit/cond/while bodies);
    returns total eqn count."""
    total = 0
    stack = [jaxpr]
    while stack:
        jx = stack.pop()
        for eq in jx.eqns:
            census[eq.primitive.name] = census.get(eq.primitive.name, 0) + 1
            total += 1
            for p in eq.params.values():
                if hasattr(p, "jaxpr"):
                    stack.append(p.jaxpr)
                elif isinstance(p, (list, tuple)):
                    for pp in p:
                        if hasattr(pp, "jaxpr"):
                            stack.append(pp.jaxpr)
    return total


def _static_peak(jaxpr, traced_rows: int):
    """Sound upper bound on simultaneously-live intermediate bytes: walk
    top-level eqns in program order with last-use liveness (a buffer is
    allocated at its defining eqn and freed after its last consumer).
    XLA will fuse much of this away — that is why it is an UPPER bound;
    it cannot under-report, which is the property the plan-time
    memory_budget check needs. Returns (peak, fixed_peak, per_row_peak)
    split by whether the leading dim equals the traced batch rows."""
    last_use: dict = {}
    for i, eq in enumerate(jaxpr.eqns):
        for v in eq.invars:
            if hasattr(v, "aval") and type(v).__name__ != "Literal":
                last_use[id(v)] = i
    for v in jaxpr.outvars:
        if hasattr(v, "aval") and type(v).__name__ != "Literal":
            last_use[id(v)] = len(jaxpr.eqns)

    live = 0
    live_row = 0
    peak = 0
    peak_fixed = 0
    peak_row = 0
    expiring: dict = {}
    for i, eq in enumerate(jaxpr.eqns):
        for v in eq.outvars:
            aval = getattr(v, "aval", None)
            if aval is None:
                continue
            nb = _aval_nbytes(aval)
            scales = bool(aval.shape) and traced_rows > 0 \
                and aval.shape[0] == traced_rows
            live += nb
            if scales:
                live_row += nb
            end = last_use.get(id(v), i)  # unused: dies immediately
            expiring.setdefault(end, []).append((nb, scales))
        if live > peak:
            peak = live
            peak_row = live_row
            peak_fixed = live - live_row
        for nb, scales in expiring.pop(i, ()):
            live -= nb
            if scales:
                live_row -= nb
    per_row = peak_row // max(traced_rows, 1)
    return peak, peak_fixed, per_row


def _str_buf_count(jaxpr) -> int:
    """Count distinct >=2-d uint8 buffers in the stage's live row state:
    the widest optimization_barrier (operator-boundary materialization)
    when present, else the outvars."""
    best = None
    best_w = -1
    for eq in jaxpr.eqns:
        if eq.primitive.name == "optimization_barrier" \
                and len(eq.invars) > best_w:
            best_w = len(eq.invars)
            best = eq.invars
    if best is None:
        best = jaxpr.outvars
    n = 0
    for v in best:
        aval = getattr(v, "aval", None)
        if aval is not None and getattr(aval, "dtype", None) is not None \
                and aval.dtype.name == "uint8" and len(aval.shape) >= 2:
            n += 1
    return n


def _find_spans(jaxpr, names) -> Optional[tuple]:
    """(first, last) top-level eqn index whose primitive is in names."""
    first = last = None
    for i, eq in enumerate(jaxpr.eqns):
        if eq.primitive.name in names:
            if first is None:
                first = i
            last = i
    return None if first is None else (first, last)


def analyze(closed_jaxpr, *, n_ops: int = 1, platform: str = "",
            traced_rows: int = 0) -> Optional[GraphReport]:
    """Run the pass over a ClosedJaxpr. Returns None when the gate is
    off (the zero-alloc disabled path — callers treat None as "no
    findings, no veto"). ``platform`` guards the CPU-only wedge rule;
    ``traced_rows`` is the leading batch dim of the traced avals (8 for
    the plan-time probe shapes) and drives the per-row peak split."""
    if not _enabled:
        return None
    t0 = time.perf_counter()
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)

    census: dict = {}
    n_eqns = _walk_census(jaxpr, census)
    families: dict = {}
    for prim, cnt in census.items():
        fam = family_of(prim)
        families[fam] = families.get(fam, 0) + cnt

    if traced_rows <= 0:
        for v in jaxpr.invars:
            aval = getattr(v, "aval", None)
            if aval is not None and getattr(aval, "shape", ()):
                traced_rows = int(aval.shape[0])
                break
    peak, peak_fixed, per_row = _static_peak(jaxpr, traced_rows)
    str_bufs = _str_buf_count(jaxpr)
    in_row = 0
    if traced_rows > 0:
        for v in jaxpr.invars:
            aval = getattr(v, "aval", None)
            if aval is not None and getattr(aval, "shape", ()) \
                    and aval.shape[0] == traced_rows:
                in_row += _aval_nbytes(aval)
        in_row //= traced_rows

    report = GraphReport(
        n_eqns=n_eqns, n_ops=max(n_ops, 1), census=census,
        families=families, peak_bytes=peak, peak_fixed_bytes=peak_fixed,
        peak_row_bytes=per_row, input_row_bytes=in_row,
        traced_rows=traced_rows, str_bufs=str_bufs)

    score = sum(FAMILY_WEIGHTS.get(f, 0.0015) * c
                for f, c in families.items())
    compaction = census.get("scatter", 0) + census.get("cumsum", 0)
    eqns_per_op = n_eqns / max(n_ops, 1)

    # ---- named rules -------------------------------------------------
    is_cpu = (platform or "").startswith("cpu")
    if is_cpu and eqns_per_op >= WEDGE_MIN_EQNS_PER_OP \
            and compaction >= WEDGE_MIN_COMPACTION \
            and str_bufs >= WEDGE_MIN_STR_BUFS:
        span = _find_spans(jaxpr, ("scatter", "cumsum"))
        report.findings.append(Finding(
            "wide-str-compaction", "wedge",
            f"{eqns_per_op:.0f} eqns/op with {compaction} "
            f"scatter/cumsum compaction eqns over {str_bufs} string "
            f"row buffers — XLA:CPU fusion emission goes superlinear "
            f"on this shape (round-17 bisection: any post-assembly "
            f"consumer of the assembled row wedges the compile)",
            eqn_span=span))

    if compaction >= 2:
        span = _find_spans(jaxpr, ("scatter", "cumsum"))
        report.findings.append(Finding(
            "compaction-chain", "info",
            f"{census.get('scatter', 0)} scatter + "
            f"{census.get('cumsum', 0)} cumsum eqns "
            f"(string compaction / positional rewrite chain)",
            eqn_span=span))
    onehot = census.get("iota", 0)
    if onehot >= 2 and census.get("concatenate", 0) >= 2:
        report.findings.append(Finding(
            "onehot-concat-chain", "info",
            f"{onehot} iota + {census.get('concatenate', 0)} concatenate "
            f"eqns (one-hot index assembly feeding scatter/gather)"))

    # dtype creep: 8-byte eqn outputs dominating the graph
    wide = 0
    for_eqns = 0
    for eq in jaxpr.eqns:
        for v in eq.outvars:
            aval = getattr(v, "aval", None)
            if aval is None or getattr(aval, "dtype", None) is None:
                continue
            for_eqns += 1
            if aval.dtype.itemsize >= 8:
                wide += 1
    if wide >= _CREEP_MIN_COUNT and for_eqns \
            and wide / for_eqns >= _CREEP_MIN_FRACTION:
        report.findings.append(Finding(
            "dtype-creep-64bit", "info",
            f"{wide}/{for_eqns} eqn outputs are 8-byte (i64/f64) — "
            f"check for implicit Python-int/float promotion widening "
            f"intermediates"))

    # implicit-broadcast blowup: broadcasts that multiply element count
    blowups = 0
    worst_ratio = 0.0
    for eq in jaxpr.eqns:
        if eq.primitive.name != "broadcast_in_dim":
            continue
        try:
            out_sz = 1
            for d in eq.outvars[0].aval.shape:
                out_sz *= int(d)
            in_sz = 1
            for d in getattr(eq.invars[0], "aval", None).shape:
                in_sz *= int(d)
            ratio = out_sz / max(in_sz, 1)
        except Exception:
            continue
        if ratio >= _BLOWUP_RATIO:
            blowups += 1
            worst_ratio = max(worst_ratio, ratio)
    if blowups >= _BLOWUP_MIN_COUNT:
        report.findings.append(Finding(
            "broadcast-blowup", "info",
            f"{blowups} broadcasts expand element count >= "
            f"{_BLOWUP_RATIO}x (worst {worst_ratio:.0f}x) — implicit "
            f"outer-product-shaped intermediates"))

    if report.wedge:
        score = max(score, 1e9)   # a wedge outranks any threshold
    report.hazard_score = score
    report.elapsed_ms = (time.perf_counter() - t0) * 1e3
    return report


# ---------------------------------------------------------------------------
# stage-level convenience (plan plane, CLI, smoke gate)
# ---------------------------------------------------------------------------


def analyze_stage(stage, platform: str = "") -> Optional[GraphReport]:
    """Trace ``stage``'s device fn at the plan-time probe shapes and run
    the pass. Returns None when the gate is off, the stage has no
    columnar input, it is already interpreter-pinned, or the trace
    fails (the compile plane will vet the real traced jaxpr anyway)."""
    if not _enabled:
        return None
    from ..plan.physical import abstract_batch_arrays

    if getattr(stage, "force_interpret", False):
        return None
    arrays = abstract_batch_arrays(stage.input_schema)
    if arrays is None:
        return None
    try:
        from ..runtime.jaxcfg import jax

        if not platform:
            platform = jax.default_backend()
        fn = stage.build_device_fn(stage.input_schema)
        closed = jax.make_jaxpr(fn)(arrays)
    except Exception:
        return None
    rows = 0
    for v in arrays.values():
        if getattr(v, "shape", ()):
            rows = int(v.shape[0])
            break
    return analyze(closed, n_ops=len(getattr(stage, "ops", ()) or ()) or 1,
                   platform=platform, traced_rows=rows)


# ---------------------------------------------------------------------------
# plan-time vet memo (plan/physical._vet_stage)
# ---------------------------------------------------------------------------
# Drivers (and the test suite) re-plan the same pipeline shapes over and
# over; the probe trace behind analyze_stage costs ~300 ms where a plan
# without it costs ~7 ms. Verdicts are therefore memoized on the stage
# fingerprint — the compile plane's content address, which by
# construction captures everything that shapes the jaxpr (op sources,
# schemas, speculation state, codegen options). The backend is fixed per
# process (jaxcfg), so the fingerprint alone is a sufficient key.

_VET_MEMO: dict = {}
_VET_MEMO_CAP = 512
_MISS = object()


def vet_memo_get(fp: str):
    """(hit, report). The returned report is a copy with a fresh
    findings list (plan-plane annotations like ``static-peak-memory``
    must stay per-plan) and ``elapsed_ms`` 0.0 — a memo hit ran no walk,
    so it must not bill one to the stage's graphlint_ms."""
    rep = _VET_MEMO.get(fp, _MISS)
    if rep is _MISS:
        return False, None
    if rep is None:
        return True, None
    return True, replace(rep, findings=list(rep.findings), elapsed_ms=0.0)


def vet_memo_put(fp: str, report: Optional[GraphReport]) -> None:
    if len(_VET_MEMO) >= _VET_MEMO_CAP:   # unbounded plans, bounded memo
        _VET_MEMO.clear()
    _VET_MEMO[fp] = None if report is None else \
        replace(report, findings=list(report.findings))
