"""Physical planning: stage splitting + fused stage functions.

Re-designs the reference's physical layer (reference:
core/src/physical/PhysicalPlan.cc:60-238 — split DAG into stages at pipeline
breakers; StageBuilder.cc — fuse the stage's operators into one compiled
function). Here a TransformStage compiles to ONE jax function over a staged
column batch: every fused operator contributes ops to the same trace, so XLA
sees the whole pipeline and fuses it into a handful of kernels (the TPU analog
of the reference's single LLVM row-loop).
"""

from __future__ import annotations

import hashlib
from typing import Callable, Optional

import dataclasses

from ..compiler.emitter import EmitCtx, Emitter, Frame
from ..compiler.stagefn import input_row_cv, result_arrays
from ..compiler.values import CV, tuple_cv
from ..core import typesys as T
from ..core.errors import NotCompilable, exception_class_for_code
from ..runtime.jaxcfg import jnp
from . import logical as L


@dataclasses.dataclass(frozen=True)
class ResolvePlan:
    """Plan-time resolve-tier decision for one TransformStage, derived
    from the analyzer's exception-site inventory and the static type
    verdicts (see TransformStage.resolve_plan). The backend consults it
    instead of inspecting error codes after D2H:

    * ``use_general`` — whether the compiled general-case tier is worth
      dispatching at all (a widened decode exists AND a decode-speculation
      code is in the inventory). False skips the build attempt entirely —
      previously every stage paid one doomed NotCompilable trace to learn
      this.
    * ``interpreter_possible`` — whether any DEVICE-coded row can reach
      the per-row interpreter (input-boxed fallback rows are a runtime
      property and always interpret).
    * ``new_buffers()`` — per-code row buckets shaped by the inventory,
      instantiated per partition at D2H unpack time.
    """

    codes: tuple                 # sorted possible codes (ints)
    exact_codes: frozenset       # codes that are exact Python classes
    use_general: bool
    interpreter_possible: bool
    tier: str                    # none | general | interpreter | both

    def new_buffers(self) -> "ResolveBuffers":
        return ResolveBuffers(self.codes)


class ResolveBuffers:
    """Per-code resolve buckets: row index -> (code, operator id) grouped
    by exception-class code, preallocated from the plan-time inventory.
    Codes the inventory missed land in ``other`` — attribution degrades
    to the catch-all, correctness (every row is still routed) does not."""

    __slots__ = ("by_code", "other")

    def __init__(self, codes):
        self.by_code: dict[int, list] = {int(c): [] for c in codes}
        self.other: list = []

    def add(self, idx: int, code: int, op_id: int) -> None:
        buf = self.by_code.get(code)
        (buf if buf is not None else self.other).append((idx, code, op_id))

    def add_many(self, idx, packed) -> None:
        """Vectorized bucket fill from the device error lattice: `idx` are
        row positions, `packed` the raw int32 lattice values (class code in
        the low byte, operator id above — core/errors pack_device_code)."""
        import numpy as np

        idx = np.asarray(idx)
        packed = np.asarray(packed)
        codes = packed & 0xFF
        opids = packed >> 8
        known = np.zeros(len(idx), dtype=bool)
        for c, buf in self.by_code.items():
            m = codes == c
            if m.any():
                known |= m
                buf.extend(zip(idx[m].tolist(), codes[m].tolist(),
                               opids[m].tolist()))
        m = ~known
        if m.any():
            self.other.extend(zip(idx[m].tolist(), codes[m].tolist(),
                                  opids[m].tolist()))

    def internal_rows(self) -> list:
        """(idx, code, op_id) for rows whose code is NOT an exact Python
        exception class — the compiled general tier's candidate set."""
        out = [t
               for c, buf in self.by_code.items()
               if exception_class_for_code(c) is None
               for t in buf]
        out.extend(t for t in self.other
                   if exception_class_for_code(t[1]) is None)
        out.sort()
        return out

    def exact_rows(self) -> list:
        """(idx, code, op_id) for rows whose code IS an exact Python
        exception class (the no-resolver fast exit's candidate set)."""
        out = [t
               for c, buf in self.by_code.items()
               if exception_class_for_code(c) is not None
               for t in buf]
        out.extend(t for t in self.other
                   if exception_class_for_code(t[1]) is not None)
        out.sort()
        return out


class TransformStage:
    """A fused chain of row operators over one input source.

    `ops` excludes the source; Resolve/Ignore operators ride along for the
    host resolve path but emit nothing on device (reference: slow-path-only
    resolvers, StageBuilder.cc generateResolveCodePath).
    """

    def __init__(self, source: Optional[L.LogicalOperator],
                 ops: list[L.LogicalOperator], limit: int = -1,
                 input_schema: Optional[T.RowType] = None,
                 input_op: Optional[L.LogicalOperator] = None):
        self.source = source       # None => consumes previous stage's output
        self.ops = ops
        self.limit = limit
        src_like = source if source is not None else input_op
        self.input_schema = input_schema if input_schema is not None \
            else src_like.schema()
        last = ops[-1] if ops else src_like
        self.output_schema = last.schema()
        self.output_columns = last.columns()

    force_interpret = False   # set on segments around non-compilable ops
    route_reason = ""         # why force_interpret was set (analyzer verdict)
    split_decision = None     # splittuner.SplitDecision of _split_oversize
    predicted_compile_s = None  # predicted compile seconds for THIS stage/
                                # segment where the platform has a curve
                                # (history + compilestats)
    fold_op = None            # AggregateOperator whose pattern fold is fused
                              # into this stage's device fn (plan_stages)
    speculate_branches = True  # prune if/else arms the sample never took
                              # (tuplex.optimizer.speculateBranches)
    extra_expected_codes = ()  # re-specialization overlay (serve/respec):
                              # exception codes OBSERVED in live traffic
                              # folded into this stage's plan inventory —
                              # the re-speculated plan EXPECTS them, so
                              # they widen the resolve-buffer preallocation
                              # and the excprof baseline instead of reading
                              # as out-of-inventory drift forever
    respec_salt = ""          # per-tenant plan-generation salt (respec
                              # overlay): distinct stage.key() per
                              # generation so baselines/executable-cache
                              # entries never alias across generations or
                              # across tenants at different generations

    @property
    def has_resolvers(self) -> bool:
        """Whether any resolver/ignore rides this stage. Without one, a row
        whose device error code is an exact Python exception class needs no
        interpreter re-run at all — the reference likewise serializes
        (operator id, code) exception partitions straight from compiled code
        when no resolver exists (ResolveTask only runs for resolution)."""
        return any(isinstance(op, (L.ResolveOperator, L.IgnoreOperator))
                   for op in self.ops)

    def udf_reports(self) -> list:
        """Static-analysis reports for every UDF fused in this stage:
        [(op, udf attr, UDFReport)] (compiler/analyzer.py). Memoized — the
        per-UDF analysis itself is memoized per code object, so this is the
        stage-level view physical planning and explain(lint=True) share."""
        memo = getattr(self, "_udf_reports_memo", None)
        if memo is None:
            from ..compiler.analyzer import op_reports

            memo = self._udf_reports_memo = [
                (op, attr, rep)
                for op in self.ops
                for attr, rep in op_reports(op)]
        return memo

    def possible_exception_codes(self) -> list:
        """Every ExceptionCode rows of this stage can carry, known at PLAN
        time from the analyzer's exception-site inventory (no sampling):
        per-UDF sites, decode codes for fused decodes, NORMALCASEVIOLATION
        when branch speculation may prune a cold arm (rows entering one
        raise it), PYTHON_FALLBACK when any part of the stage routes to
        the interpreter."""
        from ..core.errors import ExceptionCode as EC

        codes: set = set()
        for c in self.extra_expected_codes or ():
            try:        # live-observed codes adopted by re-specialization
                codes.add(EC(int(c)))
            except ValueError:
                continue   # unknown device code: nothing to preallocate
        if self.force_interpret:
            codes.add(EC.PYTHON_FALLBACK)
        for op in self.ops:
            if isinstance(op, L.DecodeOperator):
                codes |= {EC.NULLERROR, EC.BADPARSE_STRING_INPUT,
                          EC.NORMALCASEVIOLATION}
        if self.speculation_pruned():
            codes.add(EC.NORMALCASEVIOLATION)
        for op, attr, rep in self.udf_reports():
            if isinstance(op, (L.ResolveOperator, L.IgnoreOperator)):
                continue   # slow-path-only UDFs never emit device codes
            codes |= rep.exception_codes()
            if rep.must_fallback:
                codes.add(EC.PYTHON_FALLBACK)
            # Option-typed inputs raise TypeError on the None rows
            # wherever a compiled expression consumes them (emitter
            # _unwrap_option: Python `None + 1` semantics) — a property
            # of the schema MEETING the UDF, invisible to the per-UDF
            # AST pass above. Narrowed by the column-reads analysis
            # when it has a verdict; over-approximated to any Option
            # column otherwise (soundness: the exception-plane drift
            # detector treats out-of-inventory codes as stale
            # speculation, so missing a reachable code is the worse
            # error).
            if EC.TYPEERROR not in codes:
                try:
                    sch = op.parent.schema()
                    names = list(getattr(sch, "columns", None) or [])
                    types = list(getattr(sch, "types", None) or [])
                    any_opt = any(t.is_optional() for t in types)
                    if any_opt:
                        from .optimizer import udf_read_columns

                        reads = udf_read_columns(getattr(op, attr, None))
                        if reads is None or not names:
                            codes.add(EC.TYPEERROR)
                        elif {n for n, t in zip(names, types)
                              if t.is_optional()} & set(reads):
                            codes.add(EC.TYPEERROR)
                except Exception:   # unknown schema: stay sound
                    codes.add(EC.TYPEERROR)
        return sorted(codes)

    def speculation_pruned(self) -> bool:
        """Whether branch speculation may have pruned a cold arm in this
        stage (some fused UDF's sample profile never took an arm). Over-
        approximates the emitter's arm-weight gate — sound for the resolve
        plan: the general tier stays available wherever pruned-arm rows
        could need the non-speculating vectorized re-run."""
        if not self.speculate_branches:
            return False
        for op in self.ops:
            if isinstance(op, (L.ResolveOperator, L.IgnoreOperator)):
                continue
            bp = getattr(op, "branch_profile", None)
            if bp is None:
                continue
            try:
                prof = bp()
            except Exception:
                continue
            if any(False in v for v in prof.values()):
                return True
        return False

    def resolve_plan(self) -> "ResolvePlan":
        """Plan-time resolve-tier decision (ROADMAP "per-code resolve
        preallocation"): the analyzer's exception inventory + the static
        type verdicts bound which error codes this stage can emit, so the
        backend picks its resolve tiers and preallocates per-code row
        buffers BEFORE any D2H — instead of discovering after the fetch
        that (say) the stage has no general-case decode to re-run, or
        scanning every error row twice to classify it. Memoized: the plan
        is a pure function of the stage."""
        memo = getattr(self, "_resolve_plan_memo", None)
        if memo is None:
            from ..core.errors import ExceptionCode as EC

            codes = self.possible_exception_codes()
            # the compiled general tier retires exactly two speculation
            # failure kinds, both decidable at plan time: normal-case
            # DECODE violations (needs a widened decode to re-run under)
            # and pruned-BRANCH violations (needs the non-speculating
            # re-compile, no decode required)
            has_general_decode = any(
                isinstance(op, L.DecodeOperator) and op.general is not None
                for op in self.ops)
            retirable = {EC.NORMALCASEVIOLATION, EC.BADPARSE_STRING_INPUT,
                         EC.NULLERROR}
            spec_pruned = self.speculation_pruned()
            use_general = (not self.force_interpret
                           and (spec_pruned
                                or (has_general_decode
                                    and any(c in retirable
                                            for c in codes))))
            exact_codes = frozenset(
                int(c) for c in codes
                if exception_class_for_code(int(c)) is not None)
            internal = [c for c in codes if int(c) not in exact_codes]
            # the per-row interpreter is reachable when the stage is routed
            # there outright, a resolver/ignore must run, or an internal
            # code can survive the general tier (input-boxed fallback rows
            # are a runtime property and always interpret — `statically`
            # here bounds the DEVICE-code paths only)
            interpreter_possible = bool(
                self.force_interpret or self.has_resolvers or internal)
            # fully statically typed + empty inventory: the inference
            # verdict says no device code fires at all ("none" tier)
            if not codes and not self.force_interpret:
                tier = "none"
            elif use_general and interpreter_possible:
                tier = "general+interpreter"
            elif use_general:
                tier = "general"
            elif interpreter_possible:
                tier = "interpreter"
            else:
                # only exact Python-class codes and no resolver: error rows
                # take the no-resolver exact exit, nothing ever re-runs
                tier = "exact-exit"
            memo = self._resolve_plan_memo = ResolvePlan(
                codes=tuple(int(c) for c in codes),
                exact_codes=exact_codes,
                use_general=use_general,
                interpreter_possible=interpreter_possible,
                tier=tier)
        return memo

    def resolver_suggestions(self) -> list:
        """Positive lint twin of the dead-resolver warning (ROADMAP
        lint-loop remainder): when the exception inventory proves this
        stage can ONLY raise exact Python exception classes and the
        author attached no resolver/ignore, suggest one — those rows take
        the no-resolver exact exit today and surface as unresolved
        exceptions the author may not know are recoverable. Suggested
        only when every inventoried code maps to a Python class: a stage
        that can also raise internal codes (NORMALCASEVIOLATION,
        PYTHON_FALLBACK...) gets no "can only raise" claim."""
        memo = getattr(self, "_resolver_suggestions_memo", None)
        if memo is None:
            memo = []
            if not self.has_resolvers and not self.force_interpret:
                codes = self.possible_exception_codes()
                if codes and all(
                        exception_class_for_code(int(c)) is not None
                        for c in codes):
                    names = "/".join(c.name for c in codes)
                    memo.append(
                        f"this stage can only raise {names} — consider a "
                        f".resolve() or .ignore() so those rows recover "
                        f"instead of surfacing as exceptions")
            self._resolver_suggestions_memo = memo
        return memo

    def dead_resolver_findings(self) -> list:
        """Plan-time dead-resolver lint (ROADMAP "lint-driven authoring
        loop"): [(resolver op, guarded op, reason)] for every resolver or
        ignore whose target exception code the guarded operator's
        exception inventory proves it can never raise. Advisory — dead
        resolvers cost a per-row class check on the slow path and usually
        indicate the author guards the wrong operator."""
        memo = getattr(self, "_dead_resolvers_memo", None)
        if memo is None:
            from ..compiler.analyzer import dead_resolver_reason, op_analysis

            memo = []
            for i, op in enumerate(self.ops):
                if not isinstance(op, (L.ResolveOperator, L.IgnoreOperator)):
                    continue
                # the guarded operator: nearest preceding non-resolver
                guarded = None
                for prev in reversed(self.ops[:i]):
                    if not isinstance(prev, (L.ResolveOperator,
                                             L.IgnoreOperator)):
                        guarded = prev
                        break
                if guarded is None or not isinstance(guarded, L.UDFOperator):
                    continue
                rep = op_analysis(guarded)
                if rep is None:
                    continue
                # the "no unknown callee" proof must be the call-whitelist
                # walk, NOT the type verdict's exactness: the abstract
                # interpreter swallows Undecidable in type-total contexts
                # (int()/len() args, comparisons, bare expressions), so an
                # exact verdict can coexist with an unknown call that DOES
                # raise the resolver's target
                import types as _types

                from ..compiler.analyzer import _calls_all_known

                udf = guarded.udf
                module_names = {
                    k: m.__name__.split(".")[0]
                    for k, m in getattr(udf, "globals", {}).items()
                    if isinstance(m, _types.ModuleType)}
                tree = getattr(udf, "tree", None)
                reason = dead_resolver_reason(
                    rep, op.exc_class,
                    fully_typed=tree is not None
                    and _calls_all_known(tree, module_names))
                if reason:
                    memo.append((op, guarded, reason))
            self._dead_resolvers_memo = memo
        return memo

    def python_pipeline(self, input_names: Optional[tuple] = None):
        """Cached per-stage compiled Python fallback pipeline (reference:
        PythonPipelineBuilder.cc generates one function per stage; ROUND 1
        interpreted the op list per row instead). Keyed by the RUNTIME input
        column names — the source tier binds column positions at build."""
        cache = getattr(self, "_py_pipelines", None)
        if cache is None:
            cache = self._py_pipelines = {}
        key = tuple(input_names) if input_names else None
        pipe = cache.get(key)
        if pipe is None:
            from ..compiler.pypipeline import build_python_pipeline

            pipe = cache[key] = build_python_pipeline(self.ops, key)
        return pipe

    def op_ids_of_lattice(self, packed):
        """Host side of the '#err' lattice. The device packs `class |
        position << 8` (build_device_fn: an operator's 1-based position
        in `self.ops`); this returns `class | op.id << 8` over THIS
        stage's operators, which is what ResolveBuffers, excprof and an
        ExceptionRecord read — so an executable traced for an earlier
        job's identical stage reports under the current job's operator
        ids. Position 0, or one past the stage, stays 0 (unknown
        operator). int64: an operator id is unbounded on the host."""
        import numpy as np

        table = np.array([0] + [op.id for op in self.ops], dtype=np.int64)
        packed = np.asarray(packed).astype(np.int64)
        pos = packed >> 8
        pos[(pos < 0) | (pos >= len(table))] = 0
        return (packed & 0xFF) | (table[pos] << 8)

    def key(self) -> str:
        """Cache key for the jit'd executable: operator chain + UDF sources +
        captured globals + input schema (specialization contract of the
        emitter)."""
        h = hashlib.sha256()
        h.update(self.input_schema.name.encode())
        if self.respec_salt:
            # per-generation key: a re-specialized stage must not share
            # baselines / jit-cache entries with its incumbent (the XLA
            # executable still dedups content-addressed in compilequeue,
            # so identical jaxprs cost one compile regardless)
            h.update(b"respec:")
            h.update(str(self.respec_salt).encode())
        if self.extra_expected_codes:
            h.update(repr(tuple(sorted(
                int(c) for c in self.extra_expected_codes))).encode())
        for op in self.ops:
            h.update(_op_identity(op).encode())
        if self.fold_op is not None:
            h.update(b"fold")
            h.update(_op_identity(self.fold_op).encode())
        for op in self.ops:
            # the group windows are read off the sample too
            rw = _regex_widths(op)
            if rw:
                h.update(b"rxw" + repr(sorted(rw.items())).encode())
        if self.speculate_branches:
            # the emitted kernel is specialized on the (data-dependent)
            # sample branch profile — a different dataset with the same UDF
            # chain must not reuse a kernel pruned for this one's sample
            h.update(b"specbr")
            for op in self.ops:
                h.update(_branch_profile_sig(op).encode())
        return h.hexdigest()[:16]

    # ------------------------------------------------------------------
    def build_device_fn(self, input_schema: Optional[T.RowType] = None,
                        general: bool = False,
                        compaction: bool = False,
                        fused_fold: bool = True) -> Callable:
        """The fused fast-path function: staged arrays -> output arrays +
        '#err' + '#keep'. Raises NotCompilable if any fused UDF can't compile
        (the backend then interprets every row).

        `input_schema` overrides the planned schema with the RUNTIME schema
        of the actual partitions (post-breaker/segment stages and projection-
        pruned sources differ from sample speculation).

        `general=True` builds the COMPILED middle tier: the fused decode
        types columns under the general-case (supertype) schema so normal-
        case violations stay vectorized before any per-row python
        (reference: StageBuilder.cc:1145 generateResolveCodePath;
        ResolveTask.h:31-98 tries resolve_f before the interpreter).

        `compaction=True` inserts selection-vector compaction after
        selective filters: surviving rows are gathered to the front of a
        smaller (sample-estimated, bucketed) batch so every downstream op
        touches fewer rows — the vectorized-engine analog of the
        reference's per-row short-circuit on filtered rows (its LLVM row
        loop simply skips them; a SIMD batch can't, so we shrink the
        batch). Outputs gain '#rowidx' ([B'] original positions, ascending;
        sentinel=padded input size for dead slots) and '#overflow' (bool:
        survivors exceeded the estimated bucket — host must discard and
        re-run without compaction)."""
        schema = input_schema if input_schema is not None else self.input_schema
        ops = [op for op in self.ops
               if not isinstance(op, (L.ResolveOperator, L.IgnoreOperator,
                                      L.TakeOperator))]
        out_schema = self.output_schema

        if self.force_interpret:
            raise NotCompilable(self.route_reason
                                or "stage segment forced to interpreter")
        from ..compiler.stagefn import require_traceable

        # plan-time traceability verdict (compiler/analyzer.py): raise
        # BEFORE any emitter work for UDFs statically known untraceable.
        # The general tier never speculates, so cold-arm findings that
        # branch pruning might hide on the fast path count against it.
        require_traceable(ops,
                          speculate=self.speculate_branches and not general)
        if general and not any(
                isinstance(op, L.DecodeOperator) and op.general is not None
                for op in ops) and not self.speculation_pruned():
            # nothing for a general re-run to widen: no supertype decode
            # AND no speculation-pruned arm to re-compile without pruning
            raise NotCompilable("stage has no general-case decode")

        plan = _compaction_plan(ops) if (compaction and not general) else {}
        sites: list = []        # the regex sites' engines, filled at trace
        fold_spec = None
        if fused_fold and self.fold_op is not None and not general:
            from . import aggregates as A

            fold_spec = A.recognize_fold(self.fold_op.aggregate_udf)

        def fn(arrays: dict):
            b = arrays["#rowvalid"].shape[0]
            ctx = EmitCtx(b, arrays["#rowvalid"], seed=arrays.get("#seed"))
            keep = arrays["#rowvalid"]
            row = input_row_cv(arrays, schema)
            from ..runtime.columns import user_columns

            names = user_columns(schema)
            # the '#err' lattice names an operator by its 1-based position
            # in `self.ops`, never by `op.id` (the session's counter): the
            # jaxpr of a stage is then the same text in every job, process
            # and path, and the host maps positions back onto the CURRENT
            # job's operators (`op_ids_of_lattice`)
            position = {id(op): i + 1 for i, op in enumerate(self.ops)}
            rowidx = None          # [B'] original positions after compaction
            full_err = None        # [b] error codes incl. compacted-away rows
            overflow = None
            bcur = b
            for op in ops:
                ctx.cur_op = position[id(op)]
                row, keep, names = _emit_op(ctx, op, row, keep, names,
                                            general=general,
                                            speculate=self.speculate_branches)
                row, keep = _fusion_barrier(ctx, row, keep)
                frac = plan.get(op.id)   # already margin-padded
                if frac is not None and bcur >= 8192:
                    from ..runtime.columns import bucket_size

                    target = int(b * frac) + 64
                    b2 = bucket_size(min(bcur, target), "q8")
                    if b2 < bcur:
                        (row, keep, rowidx, full_err,
                         overflow) = _compact_rows(ctx, row, keep, rowidx,
                                                   full_err, overflow,
                                                   b2, b)
                        bcur = b2
            sites[:] = ctx.regex_sites
            outs, out_t = result_arrays(row, bcur)
            outs = dict(outs)
            fin = keep & (ctx.err == 0)
            if fold_spec is not None:
                _emit_fused_fold(outs, fold_spec, row, names, fin, bcur)
            if rowidx is None:
                outs["#err"] = ctx.err
                outs["#keep"] = fin
            else:
                outs["#err"] = full_err.at[rowidx].set(ctx.err, mode="drop")
                outs["#keep"] = jnp.zeros(b, dtype=bool).at[rowidx].set(
                    fin, mode="drop")
                outs["#rowidx"] = rowidx
                outs["#overflow"] = overflow
                if "#foldok" in outs:
                    outs["#foldok"] = jnp.zeros(b, dtype=bool).at[
                        rowidx].set(outs["#foldok"], mode="drop")
            return outs

        # the HLO module reads `jit_tpx_stage_<key8>` (general tier:
        # `stagegen`), so a device trace names the stage it ran
        from ..runtime import tracing as TR

        named = TR.name_fn(fn, "stagegen" if general else "stage",
                           self.key())
        # the engines of its compiled regex sites, filled as it is traced:
        # compile:trace's attributes, and the executor's cue to count
        # `regex_fallback_rows` (it keeps them on the jitted fn)
        named.regex_sites = sites
        return named


def _fusion_barrier(ctx: EmitCtx, row: CV, keep):
    """Cap XLA fusion scope at operator boundaries.

    Without this, XLA-CPU's producer fusion pulls an entire multi-operator
    string pipeline into ONE kLoop fusion whose per-element evaluation
    recomputes [B, W]-shaped intermediates per output element — measured 24s
    instead of ~1s for the Zillow extractPrice stage. The barrier is a
    runtime no-op; it only tells the fusion pass to materialize each
    operator's outputs (the reference analog: each LLVM pipeline stage writes
    its row before the next reads it).

    TPU's fusion pass doesn't exhibit the kLoop recompute pathology, so the
    barriers default to CPU-only (see jaxcfg.fusion_barriers_enabled)."""
    from ..compiler.values import cv_arrays, cv_rebuild
    from ..runtime.jaxcfg import fusion_barriers_enabled, lax

    if not fusion_barriers_enabled():
        return row, keep

    leaves: list = []
    cv_arrays(row, leaves)
    n_row = len(leaves)
    leaves.extend((keep, ctx.err, ctx.active))
    out = lax.optimization_barrier(tuple(leaves))
    it = iter(out[:n_row])
    row2 = cv_rebuild(row, it)
    keep2, ctx.err, ctx.active = out[n_row], out[n_row + 1], out[n_row + 2]
    return row2, keep2


_COMPACT_MARGIN = 1.15   # multiplicative headroom over the sample estimate
_COMPACT_Z = 5.0         # + this many binomial standard errors (see pad())
_COMPACT_GATHER = 0.5    # gather cost in per-op-pass units


def _emit_fused_fold(outs: dict, spec, row: CV, names, fin, bcur) -> None:
    """Evaluate the recognized aggregate fold exprs against the stage's
    OUTPUT row under a fresh error context and emit identity-seeded scalar
    partials ('#fold{i}') plus the per-row ok mask ('#foldok'). Rows whose
    fold expr errs fold on the host afterwards; a NotCompilable expr simply
    omits the outputs (the aggregate stage then runs its own pass)."""
    import dataclasses

    from ..parallel.collectives import reduce_identity

    try:
        fctx = EmitCtx(bcur, fin)
        em = Emitter(fctx, spec.globals)
        rrow = row
        if rrow.elts is not None and names:
            rrow = dataclasses.replace(rrow, names=tuple(names))
        frame = Frame(em, {spec.row_param: rrow})
        datas = []
        for expr in spec.exprs:
            cv = frame.eval(expr)
            cv = frame._require_numeric(cv, "aggregate expr")
            datas.append(cv.data)
        ok = fin & (fctx.err == 0)
        for fi, (d, red) in enumerate(zip(datas, spec.reducers)):
            ident = reduce_identity(red, d.dtype.kind == "f")
            m = jnp.where(ok, d, ident)
            outs[f"#fold{fi}"] = (m.sum() if red == "sum"
                                  else m.min() if red == "min" else m.max())
        outs["#foldok"] = ok
    except NotCompilable:
        for k in list(outs):
            if k.startswith("#fold"):
                del outs[k]


def _compaction_plan(ops) -> dict[int, float]:
    """Choose WHERE to insert selection-vector compactions.

    Returns op.id -> estimated live fraction (relative to the stage input
    sample) for the chosen filters. Selection is a small exhaustive search
    over filter subsets with a unit-cost-per-op model: each operator costs
    its current batch fraction, each compaction costs a gather
    (_COMPACT_GATHER) at the pre-compaction fraction. A greedy first-filter
    compaction can block a much better later one (measured on zillow: the
    72.8% bedrooms filter starved the 53.3% type filter), hence the global
    search. Estimates come from the same operator sampling that drives type
    speculation (reference: TraceVisitor branch counts feed its cost
    decisions the same way)."""
    try:
        base_op = next((op.parents[0] for op in ops if op.parents), None)
        if base_op is None:
            return {}
        base = len(base_op.cached_sample())
        if base < 32:
            return {}
        import math

        def pad(f: float) -> float:
            # upper confidence bound on the live fraction: the fixed
            # multiplicative margin alone is <1 sigma of binomial sampling
            # noise at small fractions (q6's 1.8% live rate), so add
            # _COMPACT_Z standard errors. The variance uses a Wilson-style
            # smoothed fraction so an observed 0 still gets real headroom
            # (raw sqrt(f(1-f)) vanishes at f=0, exactly where a small
            # sample most understates the true rate).
            fs = (f * base + _COMPACT_Z ** 2 / 2) / (base + _COMPACT_Z ** 2)
            return min(1.0, f * _COMPACT_MARGIN
                       + _COMPACT_Z * math.sqrt(fs * (1.0 - fs) / base))

        fracs = {}   # position in ops -> cumulative live fraction after it
        for k, op in enumerate(ops):
            if isinstance(op, L.FilterOperator):
                fracs[k] = pad(len(op.cached_sample()) / base)
        # candidates must leave >=2 real compute ops downstream
        cand = [k for k in fracs
                if sum(1 for o in ops[k + 1:]
                       if not isinstance(o, L.SelectColumnsOperator)) >= 2]
        cand = cand[:10]
        if not cand:
            return {}

        def cost(subset) -> float:
            factor, total = 1.0, 0.0
            for k, op in enumerate(ops):
                total += factor
                if k in subset:
                    # bucketed batch after compacting here (~6% pad waste);
                    # fracs[] already carry the confidence-bound margin
                    new = min(factor, fracs[k] * 1.06 + 0.01)
                    if new < factor:
                        total += _COMPACT_GATHER * factor
                        factor = new
            return total

        best, best_cost = (), cost(())
        import itertools as _it

        for r in (1, 2, 3):
            for subset in _it.combinations(cand, r):
                c = cost(set(subset))
                if c < best_cost - 1e-9:
                    best, best_cost = subset, c
        return {ops[k].id: fracs[k] for k in best}
    except Exception:
        return {}


def _compact_rows(ctx: EmitCtx, row: CV, keep, rowidx, full_err, overflow,
                  b2: int, full_b: int):
    """Gather live rows (keep & no error) to the front of a [b2] batch.

    Maintains: `rowidx` [b2] original input positions (ascending; sentinel
    full_b in dead slots), `full_err` [full_b] error codes for rows that
    left the batch (their dual-mode routing must survive compaction), and
    `overflow` (live count exceeded b2 — results are unusable and the host
    re-runs the partition without compaction)."""
    from ..compiler.values import cv_arrays, cv_rebuild

    bcur = keep.shape[0]
    cur_orig = rowidx if rowidx is not None \
        else jnp.arange(bcur, dtype=jnp.int32)
    if full_err is None:
        full_err = ctx.err
    else:
        full_err = full_err.at[cur_orig].set(ctx.err, mode="drop")
    live = keep & (ctx.err == 0)
    idx = jnp.nonzero(live, size=b2, fill_value=bcur)[0].astype(jnp.int32)
    count = jnp.sum(live.astype(jnp.int32))
    ovf = count > b2
    overflow = ovf if overflow is None else (overflow | ovf)
    valid = jnp.arange(b2, dtype=jnp.int32) < count
    safe = jnp.minimum(idx, bcur - 1)
    new_rowidx = jnp.where(valid, jnp.take(cur_orig, safe, axis=0),
                           jnp.int32(full_b))
    leaves: list = []
    cv_arrays(row, leaves)
    gathered = [jnp.take(a, safe, axis=0) for a in leaves]
    row2 = cv_rebuild(row, iter(gathered))
    ctx.b = b2
    ctx.err = jnp.zeros(b2, dtype=jnp.int32)
    ctx.active = valid
    return row2, valid, new_rowidx, full_err, overflow


def runtime_output_columns(input_schema: T.RowType,
                           ops: list[L.LogicalOperator]):
    """Replay the name flow of _emit_op over the RUNTIME input schema (which
    may be projection-pruned), without tracing. Mirrors _emit_op's names
    handling exactly."""
    from ..runtime.columns import user_columns

    names = user_columns(input_schema)
    for op in ops:
        if isinstance(op, (L.ResolveOperator, L.IgnoreOperator,
                           L.TakeOperator)):
            continue
        if isinstance(op, L.MapOperator):
            out_cols = op.columns()
            names = tuple(out_cols) if out_cols else None
        elif isinstance(op, L.WithColumnOperator):
            if names is None:
                return None
            if op.column not in names:
                names = tuple(names) + (op.column,)
        elif isinstance(op, L.SelectColumnsOperator):
            names = tuple(op.schema().columns)
        elif isinstance(op, L.RenameColumnOperator):
            if names is not None and isinstance(op.old, str) and \
                    op.old in names:
                names = tuple(op.new if c == op.old else c for c in names)
            else:
                names = op.columns()
        elif isinstance(op, L.DecodeOperator):
            names = user_columns(op.declared)
        # MapColumn keeps names
    return names


def _emit_op(ctx: EmitCtx, op: L.LogicalOperator, row: CV, keep,
             names: Optional[tuple], general: bool = False,
             speculate: bool = False):
    prof = None
    if speculate and not general:
        # the GENERAL tier must never speculate: it is where cold-arm rows
        # land, so pruning there would bounce them straight to the
        # interpreter
        bp = getattr(op, "branch_profile", None)
        if bp is not None:
            try:
                prof = bp()
            except Exception:
                prof = None
    em = Emitter(ctx, getattr(op, "udf", None).globals
                 if getattr(op, "udf", None) else {},
                 branch_profile=prof, regex_widths=_regex_widths(op))
    frame = Frame(em, {})
    if isinstance(op, L.MapOperator):
        res = em.eval_udf(op.udf, [row])
        out_cols = op.columns()
        if res.elts is not None and out_cols and len(out_cols) == len(res.elts):
            res = tuple_cv(res.elts, names=out_cols, valid=res.valid)
            return res, keep, out_cols
        return res, keep, None
    if isinstance(op, L.FilterOperator):
        pred = em.eval_udf(op.udf, [row])
        tr = frame.truthy(pred)
        keep = keep & tr
        ctx.active = ctx.active & tr   # errors past a filter never fire
        return row, keep, names
    if isinstance(op, L.WithColumnOperator):
        if row.elts is None or names is None:
            raise NotCompilable("withColumn on unnamed row")
        val = em.eval_udf(op.udf, [row])
        elts = list(row.elts)
        nm = list(names)
        if op.column in nm:
            elts[nm.index(op.column)] = val
        else:
            elts.append(val)
            nm.append(op.column)
        return tuple_cv(elts, names=nm), keep, tuple(nm)
    if isinstance(op, L.MapColumnOperator):
        if row.elts is None or names is None:
            raise NotCompilable("mapColumn on unnamed row")
        ci = list(names).index(op.column)
        val = em.eval_udf(op.udf, [row.elts[ci]])
        elts = list(row.elts)
        elts[ci] = val
        return tuple_cv(elts, names=names), keep, names
    if isinstance(op, L.SelectColumnsOperator):
        if row.elts is None:
            raise NotCompilable("selectColumns on unnamed row")
        # resolve against the RUNTIME row names (projection pruning may have
        # shifted positions relative to the sampled schema)
        idx = []
        for c in op.selected:
            if isinstance(c, int):
                idx.append(c if c >= 0 else len(row.elts) + c)
            else:
                if names is None or c not in names:
                    raise NotCompilable(f"select: column {c!r} missing")
                idx.append(list(names).index(c))
        nm = tuple(op.schema().columns)
        return tuple_cv([row.elts[i] for i in idx], names=nm), keep, nm
    if isinstance(op, L.RenameColumnOperator):
        nm = tuple(op.schema().columns)
        if row.elts is not None:
            return tuple_cv(row.elts, names=nm, valid=row.valid), keep, nm
        return row, keep, nm
    if isinstance(op, L.DecodeOperator):
        return _emit_decode(ctx, frame, op, row, keep, general=general)
    raise NotCompilable(f"operator {type(op).__name__} not fusable")


def _emit_decode(ctx: EmitCtx, frame, op, row: CV, keep,
                 general: bool = False):
    """Vectorized normal-case cell decode (reference:
    CSVParseRowGenerator.cc codegen'd parse; here: parse kernels + err codes).
    Parse failures raise BADPARSE_STRING_INPUT; unexpected nulls NULLERROR —
    both re-run on the interpreter's general-case path."""
    from ..core.errors import ExceptionCode
    from ..ops import strings as S
    from ..runtime.columns import user_columns

    cells = row.elts if row.elts is not None else (row,)
    decl = op.declared
    if general and op.general is not None:
        decl = op.general
    elts = []
    for cv, t in zip(cells, decl.types):
        base = t.without_option() if t.is_optional() else t
        opt = t.is_optional()
        sb, sl = cv.sbytes, cv.slen
        missing = ~cv.valid if cv.valid is not None else \
            jnp.zeros(ctx.b, dtype=bool)
        is_null = missing
        for nv in op.null_values:
            is_null = is_null | S.equals(
                sb, sl, *S.broadcast_const(nv, ctx.b))
        if base is T.STR:
            if opt:
                elts.append(CV(t=T.option(T.STR), sbytes=sb, slen=sl,
                               valid=~is_null))
            else:
                frame.raise_where(is_null, ExceptionCode.NULLERROR)
                elts.append(CV(t=T.STR, sbytes=sb, slen=sl))
            continue
        if base is T.NULL:
            from ..compiler.values import null_cv

            # a non-null cell in an all-null speculated column violates the
            # normal case: send it to the interpreter's general-case path
            frame.raise_where(~is_null, ExceptionCode.NORMALCASEVIOLATION)
            elts.append(null_cv())
            continue
        if base is T.I64:
            # a cell outside i64 range violates the i64-typed column either
            # way at decode: both flags mean "not this schema" here
            val, bad, route = S.parse_i64(sb, sl)
            bad = bad | route
            out = CV(t=T.I64, data=val)
        elif base is T.F64:
            val, bad, route = S.parse_f64(sb, sl)
            bad = bad | route
            out = CV(t=T.F64, data=val)
        elif base is T.BOOL:
            low_b, low_l = S.lower(*S.strip(sb, sl))
            is_true = S.equals(low_b, low_l, *S.broadcast_const("true", ctx.b))
            is_false = S.equals(low_b, low_l,
                                *S.broadcast_const("false", ctx.b))
            bad = ~(is_true | is_false)
            out = CV(t=T.BOOL, data=is_true)
        else:
            raise NotCompilable(f"decode to {t}")
        if opt:
            frame.raise_where(bad & ~is_null,
                              ExceptionCode.BADPARSE_STRING_INPUT)
            out = CV(t=T.option(base), data=out.data, valid=~is_null)
        else:
            frame.raise_where(is_null, ExceptionCode.NULLERROR)
            frame.raise_where(bad & ~is_null,
                              ExceptionCode.BADPARSE_STRING_INPUT)
        elts.append(out)
    nm = user_columns(decl)
    if len(elts) == 1 and nm is None:
        return elts[0], keep, None
    return tuple_cv(elts, names=nm), keep, nm


class AggregateStage:
    """Pipeline-breaker stage wrapping one aggregation operator (reference:
    physical/AggregateStage.cc + LocalBackend executeAggregateStage)."""

    def __init__(self, op: L.LogicalOperator):
        self.op = op
        self.limit = -1
        self.output_schema = op.schema()
        self.output_columns = op.columns()


class JoinStage:
    """Pipeline-breaker stage wrapping a join: the build side is planned as
    its own sub-plan (reference: PhysicalPlan.cc:145-178 — build side becomes
    stage N-1 with HASHTABLE output; probe fuses into the next stage)."""

    def __init__(self, op):
        self.op = op
        self.limit = -1
        self.output_schema = op.schema()
        self.output_columns = op.columns()


def plan_stages(sink: L.LogicalOperator, options=None):
    """Walk the DAG sink→source splitting at pipeline breakers (reference:
    PhysicalPlan.cc:60-238 splitIntoAndPlanStages). Wrapped in a `plan`
    span (runtime/tracing) so planning cost shows up on the job timeline
    next to compile and execute."""
    from ..runtime import tracing as TR

    with TR.span("plan", "plan") as _sp:
        stages = _plan_stages_impl(sink, options)
        if _sp is not TR.NOOP:
            _sp.set("n_stages", len(stages))
            _sp.set("kinds", [type(s).__name__ for s in stages])
    return stages


def _plan_stages_impl(sink: L.LogicalOperator, options=None):
    chain: list[L.LogicalOperator] = []
    limit = -1
    node = sink
    # operators that materialize (cache) act as sources: stop the walk there
    while node.parents and not getattr(node, "acts_as_source", False):
        if isinstance(node, L.TakeOperator):
            limit = node.limit
        else:
            chain.append(node)
        node = node.parent
    source = node
    chain.reverse()

    # filter pushdown THROUGH joins (reference: emitPartialFilters pushes
    # key-side predicates across join boundaries) — on the extracted chain,
    # before it's cut into stages; the user's DAG is never mutated
    if options is None or options.get_bool(
            "tuplex.optimizer.filterPushdown", True):
        from .optimizer import push_filters_through_joins

        chain = push_filters_through_joins(chain)

    from ..runtime import tracing as TR

    with TR.span("plan:projection", "plan") as _sp:
        stages = _cut_and_project(chain, source, limit, options, _sp)
    return _finish_stages(stages, options)


def _cut_and_project(chain: list, source, limit: int, options, _sp) -> list:
    """The chain cut into stages at its breakers, with every column that
    no consumer reads pruned: across the joins (`project_through_joins`)
    and at the file sources (`_apply_projection`). Inside the
    `plan:projection` span, which also covers the cut itself and the
    in-stage filter rewrites that have to come between the two."""
    from ..runtime import tracing as TR
    from .optimizer import project_through_joins

    chain, crossed = project_through_joins(chain, source)
    stages: list = []
    cur: list[L.LogicalOperator] = []
    cur_source: Optional[L.LogicalOperator] = source
    cur_input_op: Optional[L.LogicalOperator] = source
    for op in chain:
        if op.is_breaker():
            if cur or cur_source is not None:
                stages.append(TransformStage(cur_source, cur,
                                             input_op=cur_input_op))
            from .joins import JoinOperator

            if isinstance(op, JoinOperator):
                stages.append(JoinStage(op))
            else:
                stages.append(AggregateStage(op))
            cur = []
            cur_source = None
            cur_input_op = op
        else:
            cur.append(op)
    if cur or cur_source is not None or not stages:
        stages.append(TransformStage(cur_source, cur, limit,
                                     input_op=cur_input_op))
    elif stages:
        stages[-1].limit = limit
    # filter pushdown within each stage (reference: optimizeFilters;
    # dropped rows stop raising downstream exceptions — same semantics
    # change the reference's tuplex.optimizer.filterPushdown makes)
    if options is None or options.get_bool(
            "tuplex.optimizer.filterPushdown", True):
        from .optimizer import filter_pushdown, split_filter_conjunctions

        for st in stages:
            if isinstance(st, TransformStage):
                # conjunction breakdown first so each clause pushes down
                # independently (reference: FilterBreakdownVisitor.cc +
                # LogicalPlan.cc emitPartialFilters)
                if options is None or options.get_bool(
                        "tuplex.optimizer.filterBreakdown", True):
                    st.ops = split_filter_conjunctions(st.ops)
                st.ops = filter_pushdown(st.ops)
    # selectivity-ordered filter runs (off by default, like the reference's
    # tuplex.optimizer.operatorReordering)
    if options is not None and options.get_bool(
            "tuplex.optimizer.operatorReordering", False):
        from .optimizer import reorder_filters

        for st in stages:
            if isinstance(st, TransformStage):
                st.ops = reorder_filters(st.ops)
    # projection pushdown into file sources (reference: csv.selectionPushdown)
    for i, st in enumerate(stages):
        if isinstance(st, TransformStage):
            out_req = None
            nxt = stages[i + 1] if i + 1 < len(stages) else None
            if isinstance(nxt, AggregateStage):
                # the aggregate declares which stage-output columns it
                # reads (keys + UDF row subscripts): dead columns stop
                # being parsed/decoded/staged (tpch q1: tax, shipdate)
                from .optimizer import agg_required_columns

                out_req = agg_required_columns(nxt.op)
            _apply_projection(st, out_req)
    if _sp is not TR.NOOP:
        srcs = [st for st in stages if isinstance(st, TransformStage)
                and hasattr(getattr(st.source, "stat", None), "columns")]
        files = [len(st.source.stat.columns) for st in srcs]
        _sp.set("sources", len(srcs)).set("file_columns", sum(files)) \
           .set("kept_columns", sum(
               len(getattr(st, "source_projection", None) or ()) or n
               for st, n in zip(srcs, files))) \
           .set("joins_crossed", crossed)
    return stages


def _finish_stages(stages: list, options) -> list:
    """Branch speculation, segmentation and the fused folds, over the
    projected stages."""
    # sample-driven branch speculation (reference: normal-case dead-branch
    # removal, RemoveDeadBranchesVisitor.cc; on by default there too).
    # Applied BEFORE segmentation so the compile probes see the same
    # speculation state the execution will.
    if options is not None and not options.get_bool(
            "tuplex.optimizer.speculateBranches", True):
        for st in stages:
            if isinstance(st, TransformStage):
                st.speculate_branches = False
    # segment each transform stage so one non-compilable UDF doesn't sink
    # the whole fused pipeline to the interpreter
    out: list = []
    for st in stages:
        if isinstance(st, TransformStage):
            for seg in segment_stage(st):
                # pre-submission jaxpr vetting (compiler/graphlint):
                # wedge-severity findings pre-degrade HERE, hazard
                # scores and the static memory bound steer the split
                rep = _vet_stage(seg, options)
                out.extend(_split_oversize(seg, options, report=rep))
        else:
            out.append(st)
    # fuse pattern-fold aggregates into the preceding transform stage's
    # device fn: identity-seeded partials come back with the stage outputs,
    # so the whole plan is ONE device pass instead of two (the reference
    # likewise sinks rows straight into per-task aggregates inside the
    # compiled pipeline — PipelineBuilder.h aggregate:398-401)
    from . import aggregates as A

    for i in range(len(out) - 1):
        st, nxt = out[i], out[i + 1]
        if (isinstance(st, TransformStage) and not st.force_interpret
                and st.limit < 0 and isinstance(nxt, AggregateStage)
                and type(nxt.op) is A.AggregateOperator
                and A.recognize_fold(nxt.op.aggregate_udf) is not None):
            st.fold_op = nxt.op
    return out


def consumer_kind(stages: list, si: int):
    """Who consumes stage `si`'s output: False (terminal / interpreter
    consumer) or the consumer kind "stage"/"join"/"agg" — the value
    execute_any's `intermediate` parameter takes. Shared by the driver
    loop (api/dataset.py) and the ahead-of-time compile planner
    (exec/local.py precompile_plan) so the two can never disagree on the
    packed-vs-handoff build variant."""
    nxt = stages[si + 1] if si + 1 < len(stages) else None
    if nxt is None or getattr(nxt, "force_interpret", False):
        return False
    if isinstance(nxt, AggregateStage):
        return "agg"
    if isinstance(nxt, JoinStage):
        return "join"
    if isinstance(nxt, TransformStage):
        return "stage"
    return False


def _apply_projection(stage: TransformStage, output_required=None) -> None:
    """Prune unread columns at the Arrow read: unread columns are never
    parsed, decoded, or staged to HBM."""
    from ..io.csvsource import CSVSourceOperator
    from .optimizer import required_source_columns

    src = stage.source
    if not isinstance(src, CSVSourceOperator):
        return
    req = required_source_columns(tuple(src.stat.columns), stage.ops,
                                  output_required)
    if req is None or len(req) >= len(src.stat.columns):
        return
    stage.source_projection = list(req)
    # prune the fused decode + the stage input schema to the projection;
    # integer selections resolve to NAMES first (positions shift when
    # columns are pruned)
    new_ops = []
    names = list(src.stat.columns)      # the unpruned row's, by position
    dead = set(names) - set(req)        # pruned source columns, as named now
    for op in stage.ops:
        if isinstance(op, L.RenameColumnOperator):
            old = op.old if isinstance(op.old, str) else names[op.old]
            names = [op.new if c == old else c for c in names]
            if old in dead:             # a rename of a pruned column
                dead.discard(old)
                dead.add(op.new)
                continue
        elif isinstance(op, L.WithColumnOperator):
            dead.discard(op.column)
            if op.column not in names:
                names.append(op.column)
        elif isinstance(op, (L.MapOperator, L.SelectColumnsOperator)):
            dead = set()                # past these, every name is read
        if isinstance(op, L.DecodeOperator) and op.parent is src:
            keep_idx = [src.stat.columns.index(c) for c in req]
            declared = T.row_of(req, [op.declared.types[i] for i in keep_idx])
            general = None
            if op.general is not None:
                general = T.row_of(req,
                                   [op.general.types[i] for i in keep_idx])
            pruned = L.DecodeOperator(src, declared, op.null_values,
                                      general=general)
            new_ops.append(pruned)
        elif isinstance(op, L.SelectColumnsOperator) and \
                any(isinstance(c, int) for c in op.selected):
            full_cols = op.parent.schema().columns
            names = [full_cols[c] if isinstance(c, int) else c
                     for c in op.selected]
            new_ops.append(L.SelectColumnsOperator(op.parent, names))
        else:
            new_ops.append(op)
    stage.input_schema = T.row_of(req, [T.option(T.STR)] * len(req))
    # RE-LINK the chain through the pruned decode (shallow copies with
    # cleared schema caches): ops still point at the unpruned DAG, and
    # consumers key off stage.output_schema/output_columns — a stale
    # unpruned schema would misalign the aggregate's key indices for
    # zero-row fallback partitions (review r4). Op ids survive the copy,
    # so metrics/history attribution is unchanged.
    import copy as _copy

    relinked = []
    prev: L.LogicalOperator = src
    for op in new_ops:
        if op.parents and op.parent is not prev:
            op = _copy.copy(op)
            op.parents = [prev]
            op._schema_cache = None
        relinked.append(op)
        prev = op
    stage.ops = relinked
    try:
        last = relinked[-1] if relinked else src
        stage.output_schema = last.schema()
        stage.output_columns = last.columns()
    except Exception:
        pass    # schema inference unchanged on failure (pre-existing state)


# compile-probe verdict memo — LRU-bounded like the plan/logical.py memos
# (grow-then-.clear() dropped every warm probe verdict at the cap)
from ..utils.lru import LruDict

_op_compiles_cache: LruDict = LruDict(4096)
import itertools as _it
_uid_counter = _it.count()


def op_compiles(op: L.LogicalOperator, input_schema: T.RowType,
                speculate: bool = True) -> bool:
    """Abstract-trace ONE operator against its input schema (tiny shapes,
    jax.eval_shape: no device work) — False if the emitter rejects it.
    Cached per (op, schema, speculation state): operators are immutable
    once planned, but the probe's verdict can depend on the branch profile
    (a pruned cold arm may hide a non-compilable construct), so the key
    carries the same profile signature the jit cache does."""
    if isinstance(op, (L.ResolveOperator, L.IgnoreOperator, L.TakeOperator)):
        return True
    from ..compiler import analyzer as _az

    rep = _az.op_analysis(op)
    if rep is not None and rep.must_fallback_now(speculate):
        # statically untraceable: route to the interpreter pipeline at PLAN
        # time — the emitter is never invoked, not even as a probe
        _az.STATS["plan_fallback_ops"] += 1
        return False
    ck = (_op_identity(op), input_schema.name,
          _branch_profile_sig(op) if speculate else None)
    hit = _op_compiles_cache.get(ck)
    if hit is not None:
        return hit
    result = _op_compiles_uncached(op, input_schema, speculate)
    _op_compiles_cache[ck] = result
    return result


def _regex_widths(op) -> dict:
    """An operator's sampled capture-group windows ({} where it has
    none: the emitter's fixed window then)."""
    rw = getattr(op, "regex_widths", None)
    if rw is None:
        return {}
    try:
        return rw()
    except Exception:
        return {}


def _branch_profile_sig(op) -> str:
    """Stable signature of an operator's sample branch observations (empty
    when the op has none). Feeds every cache whose value depends on the
    speculated kernel: stage.key() and the compile-probe cache."""
    bp = getattr(op, "branch_profile", None)
    if bp is None:
        return ""
    try:
        prof = bp()
    except Exception:
        return ""
    return repr(sorted(prof.items())) if prof else ""


def _op_identity(op: L.LogicalOperator) -> str:
    """Content identity of an operator, hashed — shared by the jit cache key
    and the compile-probe cache so the two can never disagree. Captured
    globals hash by repr; value-unfaithful reprs are why trace failures at
    EXECUTION time also fall back to the interpreter (exec/local.py)."""
    h = hashlib.sha256()
    h.update(type(op).__name__.encode())
    for udf_attr in ("udf", "combine_udf", "aggregate_udf"):
        udf = getattr(op, udf_attr, None)
        if udf is None:
            continue
        h.update(udf_attr.encode())
        h.update(udf.source.encode())
        for k in sorted(udf.globals):
            h.update(f"{k}={udf.globals[k]!r}".encode())
        if not udf.source:
            # a per-function uid (NOT id(): addresses get reused after GC)
            try:
                uid = udf.func.__dict__.setdefault(
                    "__tpx_uid__", f"u{next(_uid_counter)}")
            except (AttributeError, TypeError):
                uid = f"anon{id(udf.func)}"
            h.update(str(uid).encode())
    for attr in ("column", "selected", "old", "new", "null_values",
                 "left_column", "right_column", "how", "prefixes",
                 "suffixes", "initial", "key_columns", "limit"):
        if hasattr(op, attr):
            h.update(f"{attr}={getattr(op, attr)!r};".encode())
    if hasattr(op, "declared"):
        h.update(op.declared.name.encode())
    if getattr(op, "general", None) is not None:
        h.update(op.general.name.encode())
    return h.hexdigest()[:20]


def abstract_batch_arrays(input_schema: T.RowType):
    """Abstract 8-row DeviceBatch arrays for an input schema, or None when
    a column type has no columnar layout (the stage can't compile). Shared
    by the compile probe and the codeStats jaxpr counter."""
    from ..runtime.columns import flatten_type
    from ..runtime.jaxcfg import jax
    import numpy as np

    # '#seed': the partition's PRNG seed, staged beside every batch
    # (columns.stage_partition) and read by compiled `random` calls
    arrays: dict = {"#rowvalid": jax.ShapeDtypeStruct((8,), np.bool_),
                    "#seed": jax.ShapeDtypeStruct((), np.uint32)}
    for ci, ct in enumerate(input_schema.types):
        for path, lt in flatten_type(ct, str(ci)):
            base = lt.without_option() if lt.is_optional() else lt
            opt = lt.is_optional()
            if path.endswith("#opt"):
                arrays[path] = jax.ShapeDtypeStruct((8,), np.bool_)
                continue
            if base is T.STR:
                arrays[path + "#bytes"] = jax.ShapeDtypeStruct((8, 8), np.uint8)
                arrays[path + "#len"] = jax.ShapeDtypeStruct((8,), np.int32)
            elif base in (T.BOOL,):
                arrays[path] = jax.ShapeDtypeStruct((8,), np.bool_)
            elif base is T.I64:
                arrays[path] = jax.ShapeDtypeStruct((8,), np.int64)
            elif base is T.F64:
                arrays[path] = jax.ShapeDtypeStruct((8,), np.float64)
            elif base in (T.NULL, T.EMPTYTUPLE):
                pass
            else:
                return None
            if opt and not path.endswith("#opt"):
                arrays[path + "#valid"] = jax.ShapeDtypeStruct((8,), np.bool_)
    return arrays


def stage_fingerprint(stage: TransformStage,
                      input_schema: Optional[T.RowType] = None):
    """Content address of the stage's fast-path executable over an abstract
    8-row batch (exec/compilequeue fingerprint: canonical jaxpr + hoisted
    const values + avals + platform). Stages that differ only in logical
    identity — flights' isomorphic join-probe segments, equal re-planned
    pipelines — share a fingerprint and hence ONE compiled executable.
    None when the stage has no compilable device fn. NOTE: shape-specific
    (8-row probe shapes); equal fingerprints here imply the runtime
    executables dedup too, since runtime shapes derive from the same
    inputs."""
    try:
        schema = input_schema if input_schema is not None \
            else stage.input_schema
        arrays = abstract_batch_arrays(schema)
        if arrays is None or stage.force_interpret:
            return None
        fn = stage.build_device_fn(schema)
        from ..exec.compilequeue import fingerprint_fn

        return fingerprint_fn(fn, (arrays,))
    except Exception:
        return None


def _op_compiles_uncached(op: L.LogicalOperator,
                          input_schema: T.RowType,
                          speculate: bool = True) -> bool:
    from ..runtime.jaxcfg import jax

    arrays = abstract_batch_arrays(input_schema)
    if arrays is None:
        return False

    probe = TransformStage(None, [op], input_schema=input_schema,
                           input_op=op)
    # input_op=op is wrong for schema purposes; build fn against the given
    # input schema directly
    probe.input_schema = input_schema
    probe.speculate_branches = speculate
    fn = probe.build_device_fn()
    try:
        jax.eval_shape(fn, arrays)
        return True
    except NotCompilable:
        return False
    except Exception:
        # any other trace failure: treat as non-compilable (interpreter is
        # always correct)
        return False


def _vet_stage(stage: TransformStage, options) -> object:
    """Plan-time jaxpr vetting (compiler/graphlint): trace the stage at
    the probe shapes, attach the GraphReport, and PRE-DEGRADE statically
    known compile-wedges to the interpreter before the compile plane
    ever sees them. The flights airport build side is the load-bearing
    case: its jaxpr matches the ``wide-str-compaction`` rule (round-17
    bisection — see compiler/graphlint), so instead of burning a 300 s
    deadline + SIGKILL + tier restart, the stage plans straight onto the
    tier it would have ended up on anyway. The veto is recorded as a
    content-addressed ``.hazard`` marker (stage-fingerprint keyed) so
    lint/explain/compilestats — and any later process planning the same
    stage — can see WHY without re-tracing. Returns the report (None
    when the gate is off or the stage isn't traceable)."""
    from ..compiler import graphlint as GL

    if not GL.enabled() or stage.force_interpret or not stage.ops:
        return None
    if not _vet_relevant(stage, options):
        return None
    # memo key: the jit-cache key (op identities + schema + speculation
    # state) — cheap to compute, and by the same argument as the jit
    # cache it determines the traced jaxpr (backend is fixed per
    # process, jaxcfg)
    mk = None
    try:
        mk = stage.key()
    except Exception:
        pass
    if mk is not None:
        hit, report = GL.vet_memo_get(mk)
        if hit:
            stage.graph_report = report
            if report is not None and report.wedge:
                _apply_wedge_degrade(stage, report)
            return report
    from ..runtime import tracing as TR

    with TR.span("plan:graphlint", "plan") as _sp:
        report = GL.analyze_stage(stage)
        if _sp is not TR.NOOP and report is not None:
            _sp.set("eqns", report.n_eqns) \
               .set("hazard", round(min(report.hazard_score, 1e9), 2)) \
               .set("wedge", bool(report.wedge))
    stage.graph_report = report
    if mk is not None:
        GL.vet_memo_put(mk, report)
    if report is None or not report.wedge:
        return report
    _apply_wedge_degrade(stage, report)
    return report


def _source_str_extra_bytes(stage: TransformStage) -> int:
    """Bytes a row of `stage`'s string inputs holds beyond the probe's 8
    (abstract_batch_arrays), for a stage that reads a CSV source: each
    string column at the width ingest will give it, the widest cell of
    the source's sample bucketed as harmonize_partitions does. Never
    below the probe's, so the estimate it feeds only ever falls; 0 for a
    stage that reads no CSV source."""
    from ..io.csvsource import CSVSourceOperator
    from ..runtime.columns import bucket_size

    src = stage.source
    if not isinstance(src, CSVSourceOperator):
        return 0
    stat = src.stat
    at = {c: i for i, c in enumerate(stat.columns)}
    # memo on the sniffed statistic, which later jobs over the file share
    widths = stat.__dict__.setdefault("_sampled_widths", {})
    extra = 0
    for c, t in zip(stage.input_schema.columns, stage.input_schema.types):
        t = t.without_option() if t.is_optional() else t
        if t is not T.STR or c not in at:
            continue
        if c not in widths:
            i = at[c]
            widths[c] = bucket_size(max(
                (len(r[i].encode()) for r in stat.sample_rows
                 if i < len(r)), default=0), minimum=8)
        extra += max(widths[c] - 8, 0)
    return extra


#: probe-trace admission for _vet_stage: below ALL of these a stage can
#: neither wedge nor want construct-steered splitting nor threaten the
#: memory budget, so the ~300 ms trace is skipped outright
_VET_MIN_OPS = 16              # split steering only matters on big fusions
_VET_TIGHT_BUDGET = 32 << 20   # static peak check only bites tiny budgets


def _vet_relevant(stage: TransformStage, options) -> bool:
    """Is the probe trace worth its cost for this stage? Plan-time
    vetting pays a full ``make_jaxpr`` per stage; for stages that cannot
    plausibly wedge (fewer string columns on BOTH schema edges than the
    rule's floor), cannot want a construct-steered split (too few ops),
    and cannot threaten a tight executor budget, skip it. The compile
    plane still vets the real traced jaxpr at submission, so the hard
    no-wedge-submits guarantee does not depend on this heuristic."""
    from ..compiler import graphlint as GL

    if len(stage.ops) >= _VET_MIN_OPS:
        return True
    if options is not None and options.get_size(
            "tuplex.executorMemory", 1 << 30) < _VET_TIGHT_BUDGET:
        return True
    need = GL.WEDGE_MIN_STR_BUFS
    return (_schema_has_str_cols(stage.input_schema, need)
            or _schema_has_str_cols(stage.output_schema, need))


def _schema_has_str_cols(schema, need: int) -> bool:
    """>= `need` string leaves in a RowType (the wedge's row-buffer axis,
    counted without tracing)."""
    from ..runtime.columns import flatten_type

    n = 0
    for ci, ct in enumerate(getattr(schema, "types", ()) or ()):
        for path, lt in flatten_type(ct, str(ci)):
            if path.endswith("#opt"):
                continue
            base = lt.without_option() if lt.is_optional() else lt
            if base is T.STR:
                n += 1
                if n >= need:
                    return True
    return False


def _apply_wedge_degrade(stage: TransformStage, report) -> None:
    """Pre-degrade a statically known compile-wedge to the interpreter
    and record why (stats, content-addressed ``.hazard`` marker, log).
    The marker address is the compile-plane fingerprint — expensive (it
    traces), but only ever paid for actual wedges."""
    from ..exec import compilequeue as CQ
    from ..utils.logging import get_logger

    rule = next(f.rule for f in report.findings if f.severity == "wedge")
    stage.force_interpret = True
    stage.hazard_rule = rule
    detail = "; ".join(f.line() for f in report.findings
                       if f.severity == "wedge")
    with CQ._LOCK:
        CQ.STATS["hazards_found"] += 1
        CQ.STATS["hazards_avoided"] += 1
    try:
        fp = stage_fingerprint_prevet(stage)
        if fp is not None:
            CQ.write_marker(CQ._artifact_path(fp), "hazard",
                            reason=detail, fp=fp, rule=rule,
                            plane="plan")
    except Exception:   # pragma: no cover - provenance is best-effort
        pass
    get_logger("plan").warning(
        "graphlint: stage %s pre-degraded to the interpreter (%s)",
        ",".join(type(o).__name__ for o in stage.ops), detail)


def stage_fingerprint_prevet(stage: TransformStage):
    """stage_fingerprint ignoring a vet-applied force_interpret pin (the
    `.hazard` marker must land at the address the compile plane WOULD
    have used)."""
    pinned = stage.force_interpret
    try:
        stage.force_interpret = False
        return stage_fingerprint(stage)
    finally:
        stage.force_interpret = pinned


def _split_oversize(stage: TransformStage, options,
                    report=None) -> list:
    """Split a very large fused stage into balanced sub-stages. Compile
    time scales superlinearly with graph size; two half-size executables
    can compile far faster and the intermediate rides the device-resident
    handoff.

    The split follows from the stage, the options and the platform alone
    (plan/splittuner.py): on XLA:CPU the constant compile-cost curve keeps
    the stage fused unless its predicted compile blows
    ``tuplex.tpu.compileBudgetS``, then takes the fewest segments that fit
    (the least-bad split where none does); a platform without a curve —
    every accelerator — keeps the stage fused. A hazard score past
    graphlint's veto line cuts at cost-balanced points instead, and the
    static peak-memory vetting below may tighten the cap. An explicit
    ``tuplex.tpu.maxStageOps`` (>0) overrides the curve; =0 disables
    splitting entirely."""
    max_ops = 0
    if options is not None:
        max_ops = options.get_int("tuplex.tpu.maxStageOps", -1)
    n = len(stage.ops)
    dec = None
    if report is None:
        report = getattr(stage, "graph_report", None)
    if max_ops < 0:       # auto: ask the cost function
        from ..runtime.jaxcfg import jax

        from . import splittuner as ST

        platform = jax.default_backend()
        budget = options.get_float(
            "tuplex.tpu.compileBudgetS", 480.0) if options is not None \
            else 480.0
        # a hazard score past the veto line re-plans with graphlint's
        # per-op construct costs: the budget becomes the threshold PER
        # SEGMENT, and chunk boundaries balance hazard cost, so the
        # split isolates the hazardous span instead of balancing op
        # counts (the compile plane would otherwise veto the whole
        # stage, satellite: "split around the hazardous eqn span")
        op_costs = None
        if report is not None and not report.wedge and n > 1:
            from ..compiler import graphlint as GL

            threshold = GL.hazard_threshold()
            if threshold > 0 and report.hazard_score > threshold:
                budget, op_costs = threshold, report.op_costs()
        # CPU prefers fusion (boundaries are real memcpys, compiles are
        # usually cheap) and splits ONLY when the predicted compile blows
        # the budget — flights' 43-op mega-fusion ran >20 min at >120 GB
        # on XLA:CPU.
        dec = ST.plan_split(n, budget, platform,
                            prefer_fusion=platform == "cpu",
                            op_costs=op_costs)
        stage.split_decision = dec
        stage.predicted_compile_s = dec.predicted_compile_s
        if dec.k > 1 or dec.over_budget:
            ST.log_decision(dec)
        # an over-budget verdict has nowhere cheaper to go — take the
        # least-bad split and proceed
        max_ops = dec.per if dec.k > 1 else 0
    # static peak-memory vetting (compiler/graphlint): a stage whose
    # intermediates STATICALLY exceed the MemoryManager budget at the
    # runtime batch size must not reach the device — it would OOM-spill
    # (or hard-fail) after compiling. Splitting shrinks the live set
    # proportionally to the op share; a single op that alone blows the
    # budget degrades to the interpreter, which streams rows instead of
    # materializing columnar intermediates.
    if report is not None and options is not None \
            and not stage.force_interpret and report.peak_bytes > 0:
        mem_budget = options.get_size("tuplex.executorMemory", 1 << 30)
        psize = options.get_size("tuplex.partitionSize", 4 << 20)
        # a batch holds a partition's bytes: the probe's input row, its
        # strings at the width ingest will give them (the probe's are 8
        # bytes wide, so a log line of a kilobyte read as 8 bytes put
        # millions of rows in a 32 MB partition)
        in_row = report.input_row_bytes + _source_str_extra_bytes(stage)
        est_rows = psize // max(in_row, 1) \
            if report.input_row_bytes > 0 else report.traced_rows
        peak = report.peak_bytes_at(est_rows)
        remedy = "none"
        if mem_budget > 0 and peak > mem_budget:
            from ..compiler import graphlint as GL
            from ..utils.logging import get_logger

            fit = (n * mem_budget) // peak
            if fit >= 1 and n > 1:
                max_ops = int(fit) if max_ops <= 0 \
                    else min(max_ops, int(fit))
                remedy = f"split to <={max_ops} ops/segment"
            else:
                stage.force_interpret = True
                remedy = "degraded to the interpreter"
            report.findings.append(GL.Finding(
                "static-peak-memory", "warn",
                f"static intermediate peak ~{peak >> 20} MiB at "
                f"~{est_rows} rows/batch exceeds executor memory "
                f"{mem_budget >> 20} MiB — {remedy}"))
            get_logger("plan").warning(
                "graphlint: %s", report.findings[-1].message)
        stage.static_peak = {"est_rows": int(est_rows),
                             "peak_bytes": int(peak), "remedy": remedy}
        from ..runtime import tracing as TR

        TR.instant("plan:static-peak", "plan",
                   dict(stage.static_peak, ops=n, budget=int(mem_budget)))
    if not max_ops or n <= max_ops or stage.force_interpret:
        return [stage]
    import math

    k = math.ceil(n / max_ops)
    per = math.ceil(n / k)
    # chunk boundaries must not separate an op from its trailing
    # Resolve/Ignore guards. A hazard-mode split decision carries COST-
    # balanced cut points (SplitDecision.boundaries) — honored as long as
    # nothing tightened the op cap after the decision was made.
    cuts = list(dec.boundaries) if (dec is not None and dec.boundaries
                                    and max_ops == dec.per) else None
    chunks: list[list] = [[]]
    for i, op in enumerate(stage.ops):
        if cuts is not None:
            split_here = bool(cuts) and i >= cuts[0]
        else:
            split_here = len(chunks[-1]) >= per
        if split_here and not isinstance(op, (L.ResolveOperator,
                                              L.IgnoreOperator)):
            chunks.append([])
            if cuts:
                cuts.pop(0)
        chunks[-1].append(op)
    schema = stage.input_schema
    segments: list[TransformStage] = []
    for j, ops_run in enumerate(chunks):
        if j == 0:
            seg = TransformStage(
                stage.source, ops_run,
                input_schema=schema,
                input_op=None if stage.source is not None else ops_run[0])
            if hasattr(stage, "source_projection"):
                seg.source_projection = stage.source_projection
        else:
            seg = TransformStage(None, ops_run, input_schema=schema,
                                 input_op=ops_run[0])
        seg.speculate_branches = stage.speculate_branches
        if dec is not None:
            seg.split_decision = dec
            seg.predicted_compile_s = ST.predict(platform, len(ops_run))
        if hasattr(stage, "static_peak"):     # the check that cut it
            seg.static_peak = stage.static_peak
        for op in ops_run:
            if not isinstance(op, (L.ResolveOperator, L.IgnoreOperator)):
                schema = op.schema()
        segments.append(seg)
    segments[-1].limit = stage.limit
    return segments


def segment_stage(stage: TransformStage) -> list:
    """Split a fused stage at non-compilable operators: maximal compilable
    runs stay fused on device; runs of bad operators become interpreter
    segments. Resolvers/ignores ride with the run of the op they guard."""
    if not stage.ops:
        return [stage]
    flags: list = []          # True=compilable, False=not, None=passthrough
    schemas_before: list[T.RowType] = []
    schema = stage.input_schema
    for op in stage.ops:
        schemas_before.append(schema)
        if isinstance(op, (L.ResolveOperator, L.IgnoreOperator)):
            flags.append(None)
        else:
            flags.append(op_compiles(op, schema,
                                     speculate=stage.speculate_branches))
            schema = op.schema()
    if all(f is not False for f in flags):
        return [stage]

    runs: list[list] = []     # [start_idx, [ops], bad]
    for i, (op, ok) in enumerate(zip(stage.ops, flags)):
        if ok is None and runs:
            runs[-1][1].append(op)
            continue
        bad = ok is False
        if runs and runs[-1][2] == bad:
            runs[-1][1].append(op)
        else:
            runs.append([i, [op], bad])

    segments: list[TransformStage] = []
    for j, (start, ops_run, bad) in enumerate(runs):
        if j == 0:
            # inherit the (possibly projection-pruned) input schema and the
            # source projection — rebuilding from source.schema() would undo
            # the pushdown and misalign positional decode
            seg = TransformStage(
                stage.source, ops_run,
                input_schema=stage.input_schema,
                input_op=None if stage.source is not None else ops_run[0])
            if hasattr(stage, "source_projection"):
                seg.source_projection = stage.source_projection
        else:
            seg = TransformStage(None, ops_run,
                                 input_schema=schemas_before[start],
                                 input_op=ops_run[0])
        seg.force_interpret = bad
        if bad:
            from ..compiler.analyzer import op_analysis

            reasons = []
            for op in ops_run:
                rep = op_analysis(op)
                f = rep.routing_finding(stage.speculate_branches) \
                    if rep is not None else None
                if f is not None:
                    reasons.append(f"{rep.name}: {f.reason} ({rep.loc(f)})")
            if reasons:
                seg.route_reason = "plan-time fallback — " + \
                    "; ".join(reasons)
        seg.speculate_branches = stage.speculate_branches
        segments.append(seg)
    segments[-1].limit = stage.limit
    return segments
