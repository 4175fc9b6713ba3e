"""AST → columnar-jnp abstract interpreter: the compiled fast path.

This is the TPU-native replacement for the reference's LLVM code generator
(reference: codegen/src/BlockGeneratorVisitor.cc — AST to LLVM IR with
exception branches; FunctionRegistry.h:71-205 — builtins/method codegen;
TypeAnnotatorVisitor.cc — type inference). Instead of generating IR we
symbolically execute the UDF's AST over CV column batches inside a jax trace:

  * every expression evaluates to a CV (whole-column value)
  * control flow is predicated: if/else bodies run under boolean masks and
    assignments merge with `where` — no data-dependent Python control flow
    survives into the jaxpr (XLA-friendly by construction)
  * Python exceptions become error-code lattice updates: the first error per
    row wins (matching sequential interpreter semantics), and errored rows
    drop out of the active mask (reference: branch-to-exception-block,
    CodeDefs.h:43 exception_handler_f)
  * constructs outside the supported subset raise NotCompilable — the
    operator then routes ALL rows through the interpreter path (reference:
    fallback mode via cloudpickle, python/tests/test_fallback.py)

Specialization contract: constants (needles, format widths, closure values)
are baked into the trace, so the jit cache must key on them — handled by the
stage builder hashing UDF source + captured globals.
"""

from __future__ import annotations

import ast
import dataclasses
import math as _pymath
from typing import Any, Callable, Optional

from ..core import typesys as T
from ..core.errors import (ExceptionCode, NotCompilable,
                           pack_device_code)
from ..ops import strings as S
from ..runtime.jaxcfg import jnp
from ..utils.reflection import UDFSource, get_udf_source
from .values import CV, _MISSING, const_cv, dtype_for, materialize, null_cv, tuple_cv

# loop bounds: for-loops fully unroll (static trip counts only); while-loops
# unroll to the cap with per-row exit masks — rows still looping at the cap
# raise LOOPCAPEXCEEDED and resolve exactly on the interpreter (reference:
# UnrollLoopsVisitor.cc caps at compile time too)
_FOR_UNROLL_CAP = 256
_WHILE_UNROLL_CAP = 24
_DYN_ITER_CAP = 16     # masked-unroll width for runtime-length iterables


class EmitCtx:
    """Per-stage trace state: batch size, error lattice, active mask."""

    def __init__(self, b: int, rowvalid, seed=None):
        self.b = b
        self.err = jnp.zeros(b, dtype=jnp.int32)
        self.cur_op = -1                  # set per fused op by build_device_fn
        # rows that are real + normal-case; padding/fallback slots never active
        self.active = rowvalid
        # per-partition PRNG seed (0-d uint32, staged as arrays['#seed']) for
        # compiled `random` UDFs; distinct per partition so batches don't
        # replay one sequence (reference: StandardModules.cc:30-129 types the
        # random module; draws are not CPython-sequence-exact there either)
        self.seed = seed
        self._rng_base = None
        self._rng_n = 0

    def next_rng_key(self):
        if self.seed is None:
            raise NotCompilable("random requires a staged #seed")
        from jax import random as jrandom

        if self._rng_base is None:
            self._rng_base = jrandom.key(self.seed)
        k = jrandom.fold_in(self._rng_base, self._rng_n)
        self._rng_n += 1
        return k

    def coded(self, code: ExceptionCode) -> int:
        """Pack (exception class, logical-operator id) into ONE lattice
        value (core.errors.pack_device_code owns the layout). Device
        exceptions become host-attributable with zero extra device ops
        (reference: exception partitions carry (operator id, code) pairs
        from compiled code too)."""
        return pack_device_code(int(code), self.cur_op)

    def raise_where(self, cond, code: ExceptionCode) -> None:
        hit = self.active & cond & (self.err == 0)
        self.err = jnp.where(hit, jnp.int32(self.coded(code)), self.err)
        self.active = self.active & ~hit


class Emitter:
    def __init__(self, ctx: EmitCtx, globals_: dict[str, Any],
                 branch_profile: Optional[dict] = None):
        self.ctx = ctx
        self.globals = globals_
        # sample branch observations for speculative arm pruning
        # (compiler/branchprof.py); None/{} disables speculation
        self.branch_profile = branch_profile or None

    # ------------------------------------------------------------------ UDF
    def eval_udf(self, udf: UDFSource, args: list[CV]) -> CV:
        """Evaluate a UDF body over columnar args; returns the result CV."""
        if udf.source == "":
            raise NotCompilable("no source available for UDF")
        tree = udf.tree
        params = udf.params
        if len(params) != len(args):
            # multi-param UDF over a row: spread fields across params
            if len(args) == 1 and args[0].elts is not None and \
                    len(args[0].elts) == len(params):
                args = list(args[0].elts)
            else:
                raise NotCompilable(
                    f"UDF takes {len(params)} args, got {len(args)}")
        frame = Frame(self, dict(zip(params, args)))
        frame.udf_tree = tree
        if isinstance(tree, ast.Lambda):
            return frame.eval(tree.body)
        assert isinstance(tree, ast.FunctionDef)
        frame.exec_block(tree.body)
        return frame.finalize_return()

    def inline_call(self, func: Callable, args: list[CV]) -> CV:
        """Inline a user helper function referenced from UDF globals
        (reference: ClosureEnvironment — imported/defined symbols)."""
        src = get_udf_source(func)
        if src.source == "":
            raise NotCompilable(f"no source for helper {src.name}")
        sub = Emitter(self.ctx, {**src.globals})
        return sub.eval_udf(src, args)


class Frame:
    """One UDF activation: variable env + predication state."""

    def __init__(self, emitter: Emitter, env: dict[str, CV]):
        self.em = emitter
        self.ctx = emitter.ctx
        self.env = env
        self.mask = None          # branch predicate ([B] bool) or None == all
        self.ret_val: Optional[CV] = None
        self.ret_mask = jnp.zeros(self.ctx.b, dtype=bool)
        # vectorized loop state: one dict per enclosing loop; masks stay
        # None until a row actually breaks/continues/exits so constant
        # propagation survives fully-unrolled loops
        self.loops: list[dict] = []

    # -- masks ---------------------------------------------------------------
    def active(self):
        a = self.ctx.active & ~self.ret_mask
        if self.mask is not None:
            a = a & self.mask
        for lp in self.loops:
            for k in ("brk", "cont", "done"):
                if lp[k] is not None:
                    a = a & ~lp[k]
        return a

    def _assign_pred(self):
        """Predicate under which assignments merge with the old value: branch
        mask plus 'row already left this loop iteration/loop' exclusions."""
        m = self.mask
        for lp in self.loops:
            for k in ("brk", "cont", "done"):
                if lp[k] is not None:
                    m = ~lp[k] if m is None else m & ~lp[k]
        return m

    def raise_where(self, cond, code: ExceptionCode, barrier: bool = True):
        hit = self.active() & cond & (self.ctx.err == 0)
        self.ctx.err = jnp.where(hit, jnp.int32(self.ctx.coded(code)),
                                 self.ctx.err)
        self.ctx.active = self.ctx.active & ~hit
        if not barrier:
            # speculation raises: the condition is an already-materialized
            # branch predicate, not a fused error chain — cutting fusion
            # here would cost more than it saves
            return
        # cut the error lattice's producer chain HERE: lambda UDFs and the
        # fused decode have no statement boundaries, so without this the
        # final #err kLoop fusion re-pulls (and per-element RECOMPUTES)
        # every [B, W] intermediate that fed any error condition — measured
        # ~0.5s of a 1.5s zillow batch on XLA-CPU (CPU-only: see
        # jaxcfg.fusion_barriers_enabled)
        from ..runtime.jaxcfg import stmt_barriers_enabled, lax

        if stmt_barriers_enabled():
            self.ctx.err, self.ctx.active = lax.optimization_barrier(
                (self.ctx.err, self.ctx.active))

    # ===================================================================
    # statements
    # ===================================================================
    def exec_block(self, stmts: list[ast.stmt]) -> None:
        for s in stmts:
            self.exec(s)
            self._fusion_barrier()

    def _fusion_barrier(self) -> None:
        """Materialize the frame state between statements so XLA's producer
        fusion can't inline a whole UDF body into one kLoop fusion that
        recomputes [B, W] string intermediates per output element (measured
        24x slowdown on Zillow extractPrice on XLA-CPU). optimization_barrier
        is free at runtime; fusion still happens within each statement.
        CPU-only (see jaxcfg.fusion_barriers_enabled)."""
        from .values import cv_arrays, cv_rebuild
        from ..runtime.jaxcfg import stmt_barriers_enabled, lax

        if not stmt_barriers_enabled():
            return
        leaves: list = []
        items = list(self.env.items())
        for _, cv in items:
            cv_arrays(cv, leaves)
        rv = self.ret_val
        if rv is not None:
            cv_arrays(rv, leaves)
        state = [self.ctx.err, self.ctx.active, self.ret_mask]
        if self.mask is not None:
            state.append(self.mask)
        loop_slots = []   # (loop dict, key) per materialized loop mask
        for lp in self.loops:
            for k in ("brk", "cont", "done"):
                if lp[k] is not None:
                    loop_slots.append((lp, k))
                    state.append(lp[k])
        n_cv = len(leaves)
        leaves.extend(state)
        if not leaves:
            return
        out = lax.optimization_barrier(tuple(leaves))
        it = iter(out[:n_cv])
        for name, cv in items:
            self.env[name] = cv_rebuild(cv, it)
        if rv is not None:
            self.ret_val = cv_rebuild(rv, it)
        rest = iter(out[n_cv:])
        self.ctx.err, self.ctx.active, self.ret_mask = \
            next(rest), next(rest), next(rest)
        if self.mask is not None:
            self.mask = next(rest)
        for lp, k in loop_slots:
            lp[k] = next(rest)

    def exec(self, node: ast.stmt) -> None:
        m = getattr(self, "exec_" + type(node).__name__, None)
        if m is None:
            raise NotCompilable(f"statement {type(node).__name__}")
        m(node)

    def exec_Return(self, node: ast.Return) -> None:
        val = self.eval(node.value) if node.value is not None else null_cv()
        live = self.active()
        self.ret_val = val if self.ret_val is None else \
            merge_cv(self, live, val, self.ret_val)
        self.ret_mask = self.ret_mask | live

    def finalize_return(self) -> CV:
        if self.ret_val is None:
            return null_cv()
        # rows that fell off the end of the function return None
        # (only matters if some path lacks a return)
        return self.ret_val

    def exec_Assign(self, node: ast.Assign) -> None:
        val = self.eval(node.value)
        if len(node.targets) != 1:
            raise NotCompilable("chained assignment")
        self._assign_target(node.targets[0], val)

    def _assign_target(self, tgt: ast.expr, val: CV) -> None:
        if isinstance(tgt, ast.Name):
            old = self.env.get(tgt.id)
            pred = self._assign_pred()
            if pred is not None and old is not None:
                val = merge_cv(self, pred, val, old)
            self.env[tgt.id] = val
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            if val.elts is None:
                if val.is_const and isinstance(val.const, tuple):
                    val = tuple_cv([const_cv(v) for v in val.const])
                else:
                    raise NotCompilable("unpacking non-tuple")
            if len(tgt.elts) != len(val.elts):
                raise NotCompilable("unpack arity mismatch")
            for t_i, v_i in zip(tgt.elts, val.elts):
                self._assign_target(t_i, v_i)
        else:
            raise NotCompilable(f"assign target {type(tgt).__name__}")

    def exec_AugAssign(self, node: ast.AugAssign) -> None:
        cur = self.eval(node.target)
        val = self.eval(node.value)
        res = self._binop(node.op, cur, val)
        self._assign_target(node.target, res)

    def exec_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is None:
            return
        self._assign_target(node.target, self.eval(node.value))

    def _spec_arms(self, node) -> tuple[bool, bool]:
        """(prune_then, prune_else): arms the operator's sample did not
        observe (branch speculation, reference RemoveDeadBranchesVisitor.cc:
        1-147; `branchprof.observed` says what counts: a share of enough
        trials, never a single row). An arm is prunable only with positive
        evidence the OTHER arm ran — a node the sample never reached proves
        nothing about either arm — and only when its body is worth
        skipping: predicated execution of a cheap assignment costs less
        than the violation bookkeeping."""
        prof = self.em.branch_profile
        if not prof:
            return False, False
        from .branchprof import arm_weight, branch_key

        rec = prof.get(branch_key(node))
        if rec is None:
            return False, False
        saw_t, saw_f = rec
        return (not saw_t and saw_f and arm_weight(node.body) >= 1,
                not saw_f and saw_t and bool(node.orelse)
                and arm_weight(node.orelse) >= 1)

    def exec_If(self, node: ast.If) -> None:
        prune_then, prune_else = self._spec_arms(node)
        cond = self.truthy(self.eval(node.test))
        outer = self.mask
        then_m = cond if outer is None else outer & cond
        else_m = ~cond if outer is None else outer & ~cond
        if prune_then:
            # sample never entered the then-arm: emit only the else-arm;
            # rows taking the cold arm violate the normal case and resolve
            # exactly on the general/interpreter ladder
            self.raise_where(cond, ExceptionCode.NORMALCASEVIOLATION,
                             barrier=False)
            if node.orelse:
                self.mask = else_m
                self.exec_block(node.orelse)
            self.mask = outer
            return
        if prune_else and node.orelse:
            self.raise_where(~cond, ExceptionCode.NORMALCASEVIOLATION,
                             barrier=False)
            self.mask = then_m
            self.exec_block(node.body)
            self.mask = outer
            return
        self.mask = then_m
        self.exec_block(node.body)
        if node.orelse:
            self.mask = else_m
            self.exec_block(node.orelse)
        self.mask = outer

    def exec_Expr(self, node: ast.Expr) -> None:
        # evaluate for side effects (errors); discard value
        self.eval(node.value)

    # -- loops (reference: BlockGeneratorVisitor.cc:5212 NFor, :5608 NWhile,
    # UnrollLoopsVisitor.cc, IteratorContextProxy.cc zip/enumerate) ---------
    _ITER_BUILTINS = ("range", "zip", "enumerate", "reversed")

    def exec_For(self, node: ast.For) -> None:
        # evaluate the iterable ONCE (python does; and its error ops —
        # ascii guards etc. — must not emit twice). Builtin iterator
        # constructors go through the AST-level paths instead.
        is_builtin_call = (
            isinstance(node.iter, ast.Call)
            and isinstance(node.iter.func, ast.Name)
            and node.iter.func.id in self._ITER_BUILTINS
            and node.iter.func.id not in self.env
            and node.iter.func.id not in self.em.globals)
        if is_builtin_call:
            items = self._static_iter_items(node.iter)
            dyn = None if items is not None else \
                self._dynamic_iter(node.iter)
        else:
            v = self.eval(node.iter)
            items = self._cv_iter_items(v)
            dyn = None if items is not None else self._dynamic_iter_cv(v)
        if items is None:
            self._exec_for_dynamic(node, dyn)
            return
        lp = {"brk": None, "cont": None, "done": None}
        self.loops.append(lp)
        try:
            for item in items:
                self._assign_target(node.target, item)
                self.exec_block(node.body)
                lp["cont"] = None        # continue only skips ONE iteration
            brk = lp["brk"]
        finally:
            self.loops.pop()
        self._for_orelse(node, brk)

    def _for_orelse(self, node: ast.For, brk) -> None:
        """python for-else: runs unless the loop broke (per row)."""
        if not node.orelse:
            return
        outer = self.mask
        if brk is not None:
            self.mask = ~brk if outer is None else outer & ~brk
        try:
            self.exec_block(node.orelse)
        finally:
            self.mask = outer

    def _exec_for_dynamic(self, node: ast.For, dyn) -> None:
        """for over a RUNTIME-length iterable — split results, strings, and
        enumerate/zip of those (reference: IteratorContextProxy.cc codegens
        iterator state machines; here the masked-unroll scheme of exec_While
        iterates every row to ITS OWN length). Iteration k deactivates rows
        with count <= k via the loop's `done` mask, so assignments merge and
        errors raise only for rows still iterating; rows longer than the
        unroll width raise LOOPCAPEXCEEDED and resolve exactly on the
        interpreter."""
        if dyn is None:
            raise NotCompilable("for over non-static iterable")
        count, item_at, bound = dyn
        width = self._unroll_width(count, bound)
        # python leaves the loop target unbound when the iterable is empty;
        # a pre-bound name keeps its value (the masked merge reproduces
        # that). For unbound targets the empty rows must interpret — a
        # later read would otherwise see iteration-0 garbage instead of
        # NameError.
        names = [t.id for t in ast.walk(node.target)
                 if isinstance(t, ast.Name)]
        if any(n not in self.env for n in names):
            self.raise_where(count == 0, ExceptionCode.PYTHON_FALLBACK)
        lp = {"brk": None, "cont": None, "done": None, "dyn": True}
        self.loops.append(lp)
        try:
            for k in range(width):
                lp["done"] = count <= k      # rows whose iteration is over
                self._assign_target(node.target, item_at(k))
                self.exec_block(node.body)
                lp["cont"] = None
            brk = lp["brk"]
        finally:
            self.loops.pop()
        self._for_orelse(node, brk)

    def exec_While(self, node: ast.While) -> None:
        """Bounded unrolling with per-row exit masks: rows whose condition
        still holds after the cap raise LOOPCAPEXCEEDED and resolve on the
        interpreter — semantics stay exact, long-looping rows just go slow
        (reference: TypeAnnotator loop-stability + NWhile codegen)."""
        cap = _WHILE_UNROLL_CAP
        lp = {"brk": None, "cont": None, "done": None, "dyn": True}
        self.loops.append(lp)

        def eval_cond():
            """'all' (const-True: every row continues), 'stop' (const-False:
            every active row exits), or a truthy array. Rows observed exiting
            via a false condition accumulate into lp['done'] — they power
            while-else and drop out of active()."""
            cond = self.eval(node.test)
            if cond.is_const:
                if bool(cond.const):
                    return "all"
                exiting = self.active()
                lp["done"] = exiting if lp["done"] is None \
                    else lp["done"] | exiting
                return "stop"
            tr = self.truthy(cond)
            exiting = self.active() & ~tr
            lp["done"] = exiting if lp["done"] is None \
                else lp["done"] | exiting
            return tr

        try:
            for _ in range(cap):
                state = eval_cond()
                if isinstance(state, str) and state == "stop":
                    break
                self.exec_block(node.body)
                lp["cont"] = None
            else:
                # cap reached: rows still looping cannot finish on device
                state = eval_cond()
                if not (isinstance(state, str) and state == "stop"):
                    still = jnp.ones(self.ctx.b, dtype=bool) \
                        if isinstance(state, str) else state
                    self.raise_where(still, ExceptionCode.LOOPCAPEXCEEDED)
            done = lp["done"]
        finally:
            self.loops.pop()
        if node.orelse and done is not None:
            # while-else: ONLY rows that exited via a false condition (a
            # break skips it; const-False exits were folded into `done`)
            outer = self.mask
            self.mask = done if outer is None else outer & done
            try:
                self.exec_block(node.orelse)
            finally:
                self.mask = outer

    def exec_Break(self, node: ast.Break) -> None:
        if not self.loops:
            raise NotCompilable("break outside loop")
        lp = self.loops[-1]
        live = self.active()
        lp["brk"] = live if lp["brk"] is None else lp["brk"] | live

    def exec_Continue(self, node: ast.Continue) -> None:
        if not self.loops:
            raise NotCompilable("continue outside loop")
        lp = self.loops[-1]
        live = self.active()
        lp["cont"] = live if lp["cont"] is None else lp["cont"] | live

    def _static_iter_items(self, node: ast.expr) -> Optional[list[CV]]:
        """The iterable's elements as CVs when the LENGTH is trace-static:
        const str/tuple/list/range, tuple CVs, zip/enumerate/reversed over
        those. Data-dependent lengths can't unroll -> None."""
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and not node.keywords \
                and node.func.id not in self.env \
                and node.func.id not in self.em.globals:
            # keyword forms (enumerate(start=), zip(strict=)) fall through
            # to eval_Call, which rejects keywords -> interpreter
            fname = node.func.id
            if fname == "range":
                args = [self.eval(a) for a in node.args]
                if not all(a.is_const and isinstance(a.const, int)
                           for a in args) or not 1 <= len(args) <= 3:
                    return None
                r = range(*[a.const for a in args])
                if len(r) > _FOR_UNROLL_CAP:
                    raise NotCompilable(
                        f"range({len(r)}) exceeds unroll cap")
                return [const_cv(i) for i in r]
            if fname == "zip":
                subs = [self._static_iter_items(a) for a in node.args]
                if any(s is None for s in subs) or not subs:
                    return None
                return [tuple_cv(list(t)) for t in zip(*subs)]
            if fname == "enumerate":
                if len(node.args) not in (1, 2):
                    return None
                sub = self._static_iter_items(node.args[0])
                if sub is None:
                    return None
                start = 0
                if len(node.args) == 2:
                    s = self.eval(node.args[1])
                    if not (s.is_const and isinstance(s.const, int)):
                        return None
                    start = s.const
                return [tuple_cv([const_cv(i + start), e])
                        for i, e in enumerate(sub)]
            if fname == "reversed":
                sub = self._static_iter_items(node.args[0]) \
                    if len(node.args) == 1 else None
                return None if sub is None else list(reversed(sub))
        try:
            v = self.eval(node)
        except NotCompilable:
            return None
        return self._cv_iter_items(v)

    def _cv_iter_items(self, v: CV) -> Optional[list[CV]]:
        if v.is_const:
            c = v.const
            if isinstance(c, (str, tuple, list, range)):
                if len(c) > _FOR_UNROLL_CAP:
                    raise NotCompilable("iterable exceeds unroll cap")
                return [const_cv(x) for x in c]
            return None
        if v.elts is not None and v.valid is None:
            return list(v.elts)
        return None

    def _dynamic_iter(self, node: ast.expr):
        """(count [B] int32, item_at(k) -> CV, bound | None) for
        RUNTIME-length iterables — the dynamic half of iteration
        (reference: IteratorContextProxy.cc): split results, runtime
        strings (chars), and enumerate/zip mixing those with static
        iterables. `bound` is a trace-time upper limit on count when one
        exists (static zip arm, maxsplit, string width) — the unroll uses
        it instead of the blanket cap. None when the expression isn't
        iterable this way."""
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and not node.keywords \
                and node.func.id not in self.env \
                and node.func.id not in self.em.globals:
            fname = node.func.id
            if fname == "enumerate" and len(node.args) in (1, 2):
                sub = self._dynamic_iter(node.args[0])
                if sub is None:
                    return None
                start = 0
                if len(node.args) == 2:
                    s = self.eval(node.args[1])
                    if not (s.is_const and isinstance(s.const, int)):
                        return None
                    start = s.const
                cnt, item, bound = sub
                return (cnt,
                        lambda k: tuple_cv([const_cv(k + start), item(k)]),
                        bound)
            if fname == "zip" and node.args:
                subs = []      # (None, items) static | (count, item_at) dyn
                any_dyn = False
                bound = None
                for a in node.args:
                    d = self._dynamic_iter(a)
                    if d is not None:
                        subs.append((d[0], d[1]))
                        if d[2] is not None:
                            bound = d[2] if bound is None \
                                else min(bound, d[2])
                        any_dyn = True
                        continue
                    st = self._static_iter_items(a)
                    if st is None:
                        return None
                    subs.append((None, st))
                    bound = len(st) if bound is None \
                        else min(bound, len(st))
                if not any_dyn:
                    return None
                cnt = None
                for c, _ in subs:
                    if c is None:
                        continue
                    cnt = c if cnt is None else jnp.minimum(cnt, c)
                for c, items in subs:
                    if c is None:
                        cnt = jnp.minimum(cnt, len(items))

                def zip_item(k, subs=subs):
                    parts = []
                    for c, it in subs:
                        if c is None:       # static list: clipped index
                            parts.append(it[min(k, len(it) - 1)]
                                         if it else const_cv(None))
                        else:
                            parts.append(it(k))
                    return tuple_cv(parts)

                return cnt, zip_item, bound
        try:
            v = self.eval(node)
        except NotCompilable:
            return None
        return self._dynamic_iter_cv(v)

    def _dynamic_iter_cv(self, v: CV):
        """The CV-level half of _dynamic_iter (the iterable is already
        evaluated — exec_For evaluates it exactly once)."""
        if v.kind == "split":
            return self._split_dynamic(v)
        if v.base is T.STR and not v.is_const and v.sbytes is not None:
            # char iteration over a runtime string (byte == codepoint only
            # for ASCII rows; others route via the guard)
            if v.valid is not None:
                self.raise_where(~v.valid, ExceptionCode.TYPEERROR)
            self._ascii_guard(v.sbytes, v.slen)
            sb, sl = v.sbytes, v.slen

            def char_at(k, sb=sb, sl=sl):
                kk = jnp.full(self.ctx.b, k, dtype=jnp.int32)
                bb, bl = S.slice_(sb, sl, kk, kk + 1, out_width=1)
                return CV(t=T.STR, sbytes=bb, slen=bl)

            return sl.astype(jnp.int32), char_at, int(sb.shape[1])
        return None

    def _split_dynamic(self, sv: CV):
        """Piece count + per-piece bounds for a lazy split view, computed
        ONCE with an unrolled find chain shared by every item_at(k)."""
        sb, sl = sv.sbytes, sv.slen
        sep, maxsplit = sv.names
        bound = None if maxsplit is None else maxsplit + 1
        if sep is None:
            cnt = S.ws_token_count(sb, sl).astype(jnp.int32)
            if maxsplit is not None:
                cnt = jnp.minimum(cnt, maxsplit + 1)

            def ws_item(k):
                start, stop, missing = S.ws_token_bounds(sb, sl, k)
                if maxsplit is not None and k == maxsplit:
                    stop = jnp.where(missing, stop, sl)
                bb, bl = S.slice_(sb, sl, start, stop)
                return CV(t=T.STR, sbytes=bb, slen=bl)

            return cnt, ws_item, bound
        m = len(sep)
        cnt = (S.count_const(sb, sl, sep) + 1).astype(jnp.int32)
        if maxsplit is not None:
            cnt = jnp.minimum(cnt, maxsplit + 1)
        chain = _DYN_ITER_CAP if bound is None else min(bound,
                                                        _DYN_ITER_CAP)
        starts = [jnp.zeros(self.ctx.b, dtype=jnp.int32)]
        stops = []
        for k in range(chain):
            nxt = S.find_const(sb, sl, sep, start=starts[k])
            if maxsplit is not None and k == maxsplit:
                stops.append(sl)
            else:
                stops.append(jnp.where(nxt < 0, sl, nxt))
            starts.append(jnp.where(nxt < 0, sl, nxt + m).astype(jnp.int32))

        def sep_item(k):
            if k >= len(stops):     # next() beyond the traced find chain
                raise NotCompilable("iterator past split chain")
            bb, bl = S.slice_(sb, sl, starts[k], stops[k])
            return CV(t=T.STR, sbytes=bb, slen=bl)

        return cnt, sep_item, bound

    # -- comprehensions (reference: BlockGeneratorVisitor.cc:3278
    # NListComprehension) ---------------------------------------------------
    def eval_ListComp(self, node: ast.ListComp) -> CV:
        return self._comprehension(node)

    def eval_GeneratorExp(self, node: ast.GeneratorExp) -> CV:
        return self._comprehension(node)

    def _comprehension(self, node) -> CV:
        if len(node.generators) != 1:
            raise NotCompilable("nested comprehension")
        gen = node.generators[0]
        if getattr(gen, "is_async", 0):
            raise NotCompilable("async comprehension")
        items = self._static_iter_items(gen.iter)
        if items is None:
            if isinstance(node, ast.GeneratorExp):
                # a genexp over a RUNTIME-length iterable has no static
                # shape, but the REDUCERS (sum/any/all/min/max) consume it
                # lazily with masked iteration — hand them the recipe
                dyn = self._dynamic_iter(gen.iter)
                if dyn is not None:
                    # capture the DEFINING env (a helper's genexp must not
                    # rebind free names to the consumer's locals) and a
                    # one-shot cell (python generators exhaust)
                    return CV(t=T.PYOBJECT, kind="dyngen",
                              names=(node, dyn, dict(self.env),
                                     {"consumed": False}))
            raise NotCompilable("comprehension over non-static iterable")
        saved = dict(self.env)
        outs: list[CV] = []
        try:
            for item in items:
                self._assign_target(gen.target, item)
                keep = True
                for cond_node in gen.ifs:
                    cond = self.eval(cond_node)
                    if not cond.is_const:
                        # data-dependent filter => data-dependent ARITY:
                        # no static shape exists for the result
                        raise NotCompilable(
                            "comprehension filter must be trace-constant")
                    if not bool(cond.const):
                        keep = False
                        break
                if keep:
                    outs.append(self.eval(node.elt))
        finally:
            self.env = saved   # py3 comprehension scope: target doesn't leak
        # listcomp results ARE python lists; genexp results are consumable
        # only (returning either must fall back, not decode as a tuple)
        kind = "list" if isinstance(node, ast.ListComp) else "genexp"
        return tuple_cv(outs, kind=kind)

    def eval_DictComp(self, node: ast.DictComp) -> CV:
        """{k: v for ...} with trace-constant string keys becomes a named row
        (same contract as dict literals; reference: BlockGeneratorVisitor
        comprehension + MapOperator named-output semantics)."""
        if len(node.generators) != 1:
            raise NotCompilable("nested comprehension")
        gen = node.generators[0]
        if getattr(gen, "is_async", 0):
            raise NotCompilable("async comprehension")
        items = self._static_iter_items(gen.iter)
        if items is None:
            raise NotCompilable("comprehension over non-static iterable")
        saved = dict(self.env)
        keys: list[str] = []
        vals: list[CV] = []
        try:
            for item in items:
                self._assign_target(gen.target, item)
                keep = True
                for cond_node in gen.ifs:
                    cond = self.eval(cond_node)
                    if not cond.is_const:
                        raise NotCompilable(
                            "comprehension filter must be trace-constant")
                    if not bool(cond.const):
                        keep = False
                        break
                if not keep:
                    continue
                k = self.eval(node.key)
                if not (k.is_const and isinstance(k.const, str)):
                    raise NotCompilable("dict comprehension key must be a "
                                        "trace-constant str")
                v = self.eval(node.value)
                if k.const in keys:          # python: later binding wins
                    vals[keys.index(k.const)] = v
                else:
                    keys.append(k.const)
                    vals.append(v)
        finally:
            self.env = saved
        return tuple_cv(vals, names=keys)

    def exec_Pass(self, node: ast.Pass) -> None:
        pass

    def exec_Assert(self, node: ast.Assert) -> None:
        cond = self.truthy(self.eval(node.test))
        self.raise_where(~cond, ExceptionCode.ASSERTIONERROR)

    def exec_Raise(self, node: ast.Raise) -> None:
        code = ExceptionCode.UNKNOWN
        exc = node.exc
        if isinstance(exc, ast.Call):
            exc = exc.func
        if isinstance(exc, ast.Name):
            from ..core.errors import _PY_TO_CODE

            for cls, c in _PY_TO_CODE.items():
                if cls.__name__ == exc.id:
                    code = c
                    break
        self.raise_where(jnp.ones(self.ctx.b, dtype=bool), code)

    # ===================================================================
    # expressions
    # ===================================================================
    def eval(self, node: ast.expr) -> CV:
        m = getattr(self, "eval_" + type(node).__name__, None)
        if m is None:
            raise NotCompilable(f"expression {type(node).__name__}")
        return m(node)

    def eval_Constant(self, node: ast.Constant) -> CV:
        if node.value is None or isinstance(node.value, (bool, int, float, str)):
            return const_cv(node.value)
        if isinstance(node.value, tuple):
            return const_cv(node.value)
        raise NotCompilable(f"constant {type(node.value).__name__}")

    def eval_Name(self, node: ast.Name) -> CV:
        if node.id in self.env:
            return self.env[node.id]
        if node.id in self.em.globals:
            g = self.em.globals[node.id]
            if isinstance(g, (bool, int, float, str, tuple)) or g is None:
                return const_cv(g)
            return CV(t=T.PYOBJECT, const=g)  # module/function: usable in calls
        raise NotCompilable(f"unknown name {node.id!r}")

    def eval_Tuple(self, node: ast.Tuple) -> CV:
        return tuple_cv([self.eval(e) for e in node.elts])

    def eval_List(self, node: ast.List) -> CV:
        # list literals compile as tuples for CONSUMPTION (indexing/len/
        # iteration/sum agree); kind="list" makes a list-valued RETURN
        # fall back so result typing stays exactly python (list != tuple)
        return tuple_cv([self.eval(e) for e in node.elts], kind="list")

    def eval_Dict(self, node: ast.Dict) -> CV:
        # string-keyed dict literals become named rows (reference: map with
        # dict output keeps column names, MapOperator.cc)
        keys = []
        for k in node.keys:
            if not (isinstance(k, ast.Constant) and isinstance(k.value, str)):
                raise NotCompilable("dict literal with non-str-const keys")
            keys.append(k.value)
        vals = [self.eval(v) for v in node.values]
        return tuple_cv(vals, names=keys)

    def eval_BinOp(self, node: ast.BinOp) -> CV:
        left = self.eval(node.left)
        right = self.eval(node.right)
        def _plain_tuple(cv):
            # dict CVs (named) and Option tuples (valid mask) must NOT take
            # the structural fast path: python + raises on dicts, and a
            # None tuple needs its TypeError route
            return cv.elts is not None and cv.names is None \
                and cv.valid is None
        if isinstance(node.op, ast.Add) and _plain_tuple(left) \
                and _plain_tuple(right):
            if (left.kind == "list") != (right.kind == "list"):
                raise NotCompilable("list + tuple")   # TypeError in python
            return tuple_cv(list(left.elts) + list(right.elts),
                            kind=left.kind)
        if isinstance(node.op, ast.Mult) and _plain_tuple(left) \
            and right.is_const and isinstance(right.const, int) \
                and not isinstance(right.const, bool):
            return tuple_cv(list(left.elts) * max(0, right.const),
                            kind=left.kind)
        return self._binop(node.op, left, right)

    def eval_UnaryOp(self, node: ast.UnaryOp) -> CV:
        v = self.eval(node.operand)
        if isinstance(node.op, ast.Not):
            tr = self.truthy(v)
            return CV(t=T.BOOL, data=~tr)
        if isinstance(node.op, ast.USub):
            if v.is_const:
                return const_cv(-v.const)
            v = self._require_numeric(v, "unary -")
            return CV(t=v.t, data=-v.data)
        if isinstance(node.op, ast.UAdd):
            return self._require_numeric(v, "unary +")
        raise NotCompilable("unary op")

    def eval_BoolOp(self, node: ast.BoolOp) -> CV:
        # Python value semantics with short-circuit error masking: operand
        # i+1 only "runs" (raises) where all previous operands passed/failed
        vals = []
        gate = None  # mask under which next operand is evaluated
        is_and = isinstance(node.op, ast.And)
        outer = self.mask
        for i, operand in enumerate(node.values):
            self.mask = gate if gate is not None else outer
            v = self.eval(operand)
            vals.append(v)
            tr = self.truthy(v)
            nxt = tr if is_and else ~tr
            gate = nxt if gate is None else gate & nxt
            if outer is not None:
                gate = gate & outer
        self.mask = outer
        # fold values right-to-left: result = first operand failing the gate
        result = vals[-1]
        for i in range(len(vals) - 2, -1, -1):
            tr = self.truthy(vals[i])
            take_next = tr if is_and else ~tr
            result = merge_cv(self, take_next, result, vals[i])
        return result

    def eval_Compare(self, node: ast.Compare) -> CV:
        left = self.eval(node.left)
        comps = [self.eval(c) for c in node.comparators]
        if left.is_const and all(c.is_const for c in comps):
            # const-fold (unrolled loop counters etc.); raising or exotic
            # compares fall through to the vectorized error-lattice path
            import operator as _o

            table = {ast.Eq: _o.eq, ast.NotEq: _o.ne, ast.Lt: _o.lt,
                     ast.LtE: _o.le, ast.Gt: _o.gt, ast.GtE: _o.ge}
            vals = [left.const] + [c.const for c in comps]
            try:
                ok: Optional[bool] = True
                for op, a, b in zip(node.ops, vals, vals[1:]):
                    f = table.get(type(op))
                    if f is None:
                        ok = None
                        break
                    if not f(a, b):
                        ok = False
                        break
                if ok is not None:
                    return const_cv(bool(ok))
            except Exception:
                pass
        acc = None
        for op, right in zip(node.ops, [*comps]):
            res = self._compare(op, left, right)
            acc = res if acc is None else acc & res
            left = right
        return CV(t=T.BOOL, data=acc)

    def eval_IfExp(self, node: ast.IfExp) -> CV:
        prune_then, prune_else = self._spec_arms(node)
        cond = self.truthy(self.eval(node.test))
        outer = self.mask
        if prune_then:
            self.raise_where(cond, ExceptionCode.NORMALCASEVIOLATION,
                             barrier=False)
            self.mask = ~cond if outer is None else outer & ~cond
            b = self.eval(node.orelse)
            self.mask = outer
            return b
        if prune_else:
            self.raise_where(~cond, ExceptionCode.NORMALCASEVIOLATION,
                             barrier=False)
            self.mask = cond if outer is None else outer & cond
            a = self.eval(node.body)
            self.mask = outer
            return a
        self.mask = cond if outer is None else outer & cond
        a = self.eval(node.body)
        self.mask = ~cond if outer is None else outer & ~cond
        b = self.eval(node.orelse)
        self.mask = outer
        return merge_cv(self, cond, a, b)

    def eval_Subscript(self, node: ast.Subscript) -> CV:
        val = self.eval(node.value)
        if val.kind == "split":
            if isinstance(node.slice, ast.Slice):
                raise NotCompilable("slicing a split result")
            kidx = self.eval(node.slice)
            if not (kidx.is_const and isinstance(kidx.const, int)):
                raise NotCompilable("split index must be constant")
            return self._split_item(val, kidx.const)
        # slicing
        if isinstance(node.slice, ast.Slice):
            return self._slice(val, node.slice)
        idx = self.eval(node.slice)
        # tuple/row indexing
        if val.elts is not None:
            if idx.is_const and isinstance(idx.const, str):
                if val.names is None or idx.const not in val.names:
                    self_names = val.names or ()
                    raise NotCompilable(
                        f"column {idx.const!r} not in {self_names}")
                return val.elts[val.names.index(idx.const)]
            if idx.is_const and isinstance(idx.const, (int, bool)):
                i = int(idx.const)
                if not -len(val.elts) <= i < len(val.elts):
                    raise NotCompilable("tuple index out of range")
                return val.elts[i]
            raise NotCompilable("dynamic tuple index")
        if val.is_const and isinstance(val.const, dict):
            if idx.is_const:
                if idx.const in val.const:
                    return const_cv(val.const[idx.const])
                raise NotCompilable("missing dict key")
            raise NotCompilable("dynamic dict key")
        # string indexing
        if val.base is T.STR:
            val = self._unwrap_option(val, "subscript")
            self._ascii_guard(val.sbytes, val.slen)
            idx = self._require_numeric(idx, "string index")
            idx_arr = self._as_i64(idx)
            ch, cl, oob = S.char_at(val.sbytes, val.slen, idx_arr.astype(jnp.int32))
            self.raise_where(oob, ExceptionCode.INDEXERROR)
            return CV(t=T.STR, sbytes=ch, slen=cl)
        raise NotCompilable(f"subscript on {val.t}")

    def eval_Attribute(self, node: ast.Attribute) -> CV:
        val = self.eval(node.value)
        if val.is_const and val.const is not None and not isinstance(
                val.const, (bool, int, float, str, tuple)):
            # module attribute: math.pi etc.
            obj = val.const
            if hasattr(obj, node.attr):
                attr = getattr(obj, node.attr)
                if isinstance(attr, (bool, int, float, str)):
                    return const_cv(attr)
                return CV(t=T.PYOBJECT, const=attr)
        raise NotCompilable(f"attribute {node.attr}")

    def eval_Call(self, node: ast.Call) -> CV:
        if node.keywords:
            raise NotCompilable("keyword arguments")
        # method call: obj.method(args)
        if isinstance(node.func, ast.Attribute):
            # module functions (math.floor etc.) come through eval_Attribute
            try:
                recv = self.eval(node.func.value)
            except NotCompilable:
                recv = None
            if recv is not None and recv.kind == "match":
                args = [self.eval(a) for a in node.args]
                return self._match_method(recv, node.func.attr, args)
            if recv is not None and recv.is_const and \
                    getattr(recv.const, "__name__", None) == "re" and \
                    node.func.attr in ("search", "match"):
                args = [self.eval(a) for a in node.args]
                return self._re_search(node.func.attr, args)
            if recv is not None and recv.is_const and \
                    getattr(recv.const, "__name__", None) == "re" and \
                    node.func.attr == "sub":
                args = [self.eval(a) for a in node.args]
                return self._re_sub(args)
            if recv is not None and recv.is_const and \
                    getattr(recv.const, "__name__", None) == "random" and \
                    type(recv.const).__name__ == "module":
                args = [self.eval(a) for a in node.args]
                return self._random_fn(node.func.attr, args)
            if recv is not None and recv.base is T.STR:
                args = [self.eval(a) for a in node.args]
                return self._str_method(recv, node.func.attr, args)
            if recv is not None and recv.is_const and recv.const is not None \
                    and not isinstance(recv.const, (bool, int, float, str, tuple)):
                fn = getattr(recv.const, node.func.attr, None)
                if fn is not None:
                    args = [self.eval(a) for a in node.args]
                    import types as _types

                    if isinstance(fn, _types.FunctionType) and \
                            getattr(fn, "__module__", "") != "math":
                        # module-qualified user helper: inline like a bare
                        # name (ClosureEnvironment semantics); stdlib
                        # functions our registry covers (string.capwords)
                        # fall through to their device kernels
                        try:
                            return self.em.inline_call(fn, args)
                        except NotCompilable:
                            pass
                    return self._module_fn(fn, args)
            if recv is not None and recv.elts is not None \
                    and recv.names is not None:
                args = [self.eval(a) for a in node.args]
                return self._dict_method(node, recv, node.func.attr, args)
            if recv is not None and recv.elts is not None \
                    and node.func.attr in ("index", "count"):
                args = [self.eval(a) for a in node.args]
                return self._tuple_method(recv, node.func.attr, args)
            raise NotCompilable(f"method {node.func.attr}")
        if not isinstance(node.func, ast.Name):
            raise NotCompilable("computed call target")
        name = node.func.id
        args = [self.eval(a) for a in node.args]
        # python name resolution order: locals, then globals, THEN builtins —
        # a user-defined sum/len/etc. must win over our builtin emitters
        if name in self.env:
            raise NotCompilable(f"call to local value {name}")
        if name in self.em.globals:
            g = self.em.globals[name]
            if callable(g):
                if g.__module__ in ("math",):
                    return self._module_fn(g, args)
                return self.em.inline_call(g, args)
            raise NotCompilable(f"call to non-callable global {name}")
        builtin = getattr(self, "_builtin_" + name, None)
        if builtin is not None:
            return builtin(args)
        raise NotCompilable(f"call to {name}")

    def _random_fn(self, fname: str, args: list[CV]) -> CV:
        """Compiled `random` module calls (reference: FunctionRegistry
        codegens random.choice; StandardModules.cc:30-129 types the module).
        Draws use jax's counter-based PRNG keyed per (partition seed, call
        site) — deterministic per partition, distinct across partitions, and
        explicitly NOT CPython-Mersenne-sequence-exact (the reference's
        compiled path diverges from CPython sequences the same way)."""
        from jax import random as jrandom

        if fname == "random":
            if args:
                raise NotCompilable("random.random arity")
            u = jrandom.uniform(self.ctx.next_rng_key(), (self.ctx.b,),
                                dtype=jnp.float64)
            return CV(t=T.F64, data=u)
        if fname == "uniform":
            if len(args) != 2:
                raise NotCompilable("random.uniform arity")
            a = self._require_numeric(args[0], "random.uniform")
            b = self._require_numeric(args[1], "random.uniform")
            af = self._cast(a.data, T.F64)
            bf = self._cast(b.data, T.F64)
            u = jrandom.uniform(self.ctx.next_rng_key(), (self.ctx.b,),
                                dtype=jnp.float64)
            # CPython formula: a + (b-a) * random()
            return CV(t=T.F64, data=af + (bf - af) * u)
        if fname in ("randint", "randrange"):
            if fname == "randrange" and len(args) == 1:
                args = [const_cv(0), args[0]]
            if len(args) != 2:
                raise NotCompilable(f"random.{fname} arity")
            for arg in args:
                # CPython raises per-version (ValueError/TypeError) on float
                # bounds; the interpreter tier owns that exactness
                if arg.base not in (T.I64, T.BOOL):
                    raise NotCompilable(f"random.{fname} non-integer bound")
            a = self._as_i64(self._require_numeric(args[0], fname))
            b = self._as_i64(self._require_numeric(args[1], fname))
            hi = b + 1 if fname == "randint" else b    # randint is inclusive
            self.raise_where(jnp.broadcast_to(a >= hi, (self.ctx.b,)),
                             ExceptionCode.VALUEERROR)
            hi_safe = jnp.maximum(hi, a + 1)           # keep errored rows legal
            v = jrandom.randint(self.ctx.next_rng_key(), (self.ctx.b,),
                                a, hi_safe, dtype=jnp.int64)
            return CV(t=T.I64, data=v)
        if fname == "choice":
            if len(args) != 1:
                raise NotCompilable("random.choice arity")
            items = self._cv_iter_items(args[0])
            if items is None:
                raise NotCompilable("random.choice over non-static iterable")
            if not items:
                self.raise_where(jnp.ones(self.ctx.b, dtype=bool),
                                 ExceptionCode.INDEXERROR)
                return const_cv(None)
            idx = jrandom.randint(self.ctx.next_rng_key(), (self.ctx.b,),
                                  0, len(items), dtype=jnp.int32)
            acc = items[-1]
            for i in range(len(items) - 2, -1, -1):
                acc = merge_cv(self, idx == i, items[i], acc)
            return acc
        raise NotCompilable(f"random.{fname}")

    def _re_search(self, fname: str, args: list[CV]) -> CV:
        """Compiled re.search/re.match over a string column (reference:
        FunctionRegistry.h:71-205 codegens re.search; here the pattern
        compiles to whole-column kernel steps — ops/regex.py). Rows whose
        match needs deeper backtracking than the compiled engine explores
        raise PYTHON_FALLBACK and resolve exactly on the interpreter."""
        from ..ops.regex import compile_regex

        if len(args) != 2:
            raise NotCompilable("re.search arity")
        pat, s = args
        if not (pat.is_const and isinstance(pat.const, str)):
            raise NotCompilable("dynamic regex pattern")
        pattern = pat.const
        if fname == "match" and not pattern.startswith("^"):
            pattern = "^" + pattern   # re.match anchors implicitly
        try:
            rx = compile_regex(pattern)   # anchored engine: capture groups
        except NotCompilable:
            rx = None                     # NFA below: boolean-only
        if s.base is not T.STR:
            raise NotCompilable("re.search over non-string")
        if s.valid is not None:
            # python: re.search(p, None) raises TypeError
            self.raise_where(~s.valid, ExceptionCode.TYPEERROR)
        if any(ord(c) > 127 for c in pattern):
            raise NotCompilable("non-ASCII regex pattern")
        # byte-space matching diverges from codepoint semantics on
        # multibyte rows: route them to the interpreter
        s = materialize(s, self.ctx.b)
        self._ascii_guard(s.sbytes, s.slen)
        sb, sl = s.sbytes, s.slen
        if rx is None:
            # unanchored / alternation patterns: exact EXISTENCE via the
            # bit-parallel NFA (ops/nfa.py).
            from ..ops.nfa import compile_nfa

            nfa = compile_nfa(pattern)
            # two-pass capture groups (reference codegens re.search
            # generally, FunctionRegistry.h:184-205): the NFA's min-plus
            # scan finds python's leftmost match START (its boolean is the
            # same exact existence answer, so one scan serves both), then
            # the anchored engine re-runs at that offset for the greedy
            # group spans. The second pass is LAZY — a UDF that only uses
            # the match as a boolean never pays the anchored engine or its
            # fallback routing.
            rx2 = None
            if not nfa.anchored_start and not nfa.nullable \
                    and nfa.n_pos <= nfa._START_MAX_POS:
                try:
                    rx2 = compile_regex("^" + pattern)
                except NotCompilable:
                    rx2 = None
            if rx2 is None:
                # boolean-only: exact existence via the bit-parallel
                # engine; .group() raises NotCompilable and the whole UDF
                # interprets
                return CV(t=T.option(T.tuple_of(T.STR)), elts=(),
                          valid=nfa.match(sb, sl), kind="match")
            matched, start = nfa.match_start(sb, sl)
            cell: list = []

            def _two_pass():
                if not cell:
                    shb, shl = S.slice_(sb, sl, start, sl)
                    am, suspect, gs, ge = rx2.match(shb, shl)
                    elts = []
                    for g in range(rx2.n_groups + 1):
                        bb, bl = S.slice_(shb, shl, gs[g], ge[g])
                        elts.append(CV(t=T.STR, sbytes=bb, slen=bl))
                    # fail-safe: the anchored engine's single-retreat
                    # backtracking may fall short at the found offset —
                    # those rows interpret (raised by the consumer)
                    cell.append((tuple(elts),
                                 matched & (suspect | ~am)))
                return cell[0]

            return CV(t=T.option(T.tuple_of(*[T.STR] *
                                            (rx2.n_groups + 1))),
                      elts=(), valid=matched, kind="match",
                      names=("#lazy_groups", _two_pass))
        matched, suspect, gs, ge = rx.match(sb, sl)
        self.raise_where(suspect & ~matched, ExceptionCode.PYTHON_FALLBACK)
        t_match = T.option(T.tuple_of(*[T.STR] * (rx.n_groups + 1)))
        win = self._GROUP_WIN
        if sb.shape[1] <= win:
            elts = []
            for g in range(rx.n_groups + 1):
                bb, bl = S.slice_(sb, sl, gs[g], ge[g])
                elts.append(CV(t=T.STR, sbytes=bb, slen=bl))
            return CV(t=t_match, elts=tuple(elts), valid=matched,
                      kind="match")
        # wide sources: capture groups slice to _GROUP_WIN instead of the
        # source width — every downstream pass over a group column
        # (parses, compares, output buffers, boxing) then reads 48
        # bytes/row, not W. Rows with a longer group ROUTE in ONE combined
        # raise (fail-safe, same contract as ops.strings._PARSE_WIN;
        # per-group raises fragmented statement fusion 4x). Slicing AND
        # routing are LAZY like the unanchored path: boolean-only
        # consumers keep every row on device. Group 0 (the whole match)
        # keeps full width.
        cell: list = []

        def _groups():
            if not cell:
                over = jnp.zeros(self.ctx.b, dtype=bool)
                for g in range(1, rx.n_groups + 1):
                    over = over | (ge[g] - gs[g] > win)
                elts = []
                for g in range(rx.n_groups + 1):
                    bb, bl = S.slice_(sb, sl, gs[g], ge[g],
                                      out_width=win if g else None)
                    elts.append(CV(t=T.STR, sbytes=bb, slen=bl))
                cell.append((tuple(elts), matched & over))
            return cell[0]

        return CV(t=t_match, elts=(), valid=matched, kind="match",
                  names=("#lazy_groups", _groups))

    _GROUP_WIN = 48

    def _re_sub(self, args: list[CV]) -> CV:
        """Compiled re.sub for the class-run subset ('[class]+' / '\\d+' /
        '\\s+' style — one character class repeated at least once, the
        common data-cleaning shape; reference: FunctionRegistry re.sub).
        Everything else falls back to the interpreter."""
        if len(args) != 3:
            raise NotCompilable("re.sub arity")
        pat, repl, s = args
        if not (pat.is_const and isinstance(pat.const, str)):
            raise NotCompilable("dynamic regex pattern")
        if not (repl.is_const and isinstance(repl.const, str)):
            raise NotCompilable("re.sub dynamic replacement")
        if "\\" in repl.const:
            raise NotCompilable("re.sub backreference replacement")
        if s.valid is not None:
            self.raise_where(~s.valid, ExceptionCode.TYPEERROR)
        s = materialize(s, self.ctx.b)
        rb, rl = self._to_strpair(s)
        self._ascii_guard(rb, rl)
        table = _class_run_table(pat.const)
        if table is not None:
            fb, fl = S.replace_class_runs(rb, rl, table, repl.const)
            return CV(t=T.STR, sbytes=fb, slen=fl)
        return self._re_sub_general(pat.const, repl.const, rb, rl)

    _RE_SUB_MAX_MATCHES = 8

    def _re_sub_general(self, pattern: str, new: str, rb, rl) -> CV:
        """General multi-element re.sub (VERDICT r4 #5; reference codegens
        re.sub generally, FunctionRegistry.h:184-205): python's scan loop —
        find leftmost match, replace, continue at its end — vectorized as a
        bounded unroll. Each round the NFA min-plus scan locates the next
        match start on the remaining suffix, the anchored engine supplies
        the greedy end, and splice_spans assembles the output in one pass.
        Rows with more than _RE_SUB_MAX_MATCHES matches (or needing deeper
        backtracking) route to the interpreter — fail-safe, never wrong."""
        from ..ops.nfa import compile_nfa
        from ..ops.regex import compile_regex

        nfa = compile_nfa(pattern)
        if nfa.anchored_start:
            # ^/\A patterns replace at most the one leftmost match; the
            # suffix-restart loop would wrongly re-anchor every round
            raise NotCompilable("re.sub of anchored pattern")
        if nfa.nullable or not 0 < nfa.n_pos <= nfa._START_MAX_POS:
            raise NotCompilable("re.sub pattern outside compiled bounds")
        rx2 = compile_regex("^" + pattern)   # may raise NotCompilable
        b = self.ctx.b
        zero = jnp.zeros(b, dtype=rl.dtype)
        o = zero
        active = jnp.ones(b, dtype=bool)
        suspect = jnp.zeros(b, dtype=bool)
        starts, ends, valids = [], [], []
        for _ in range(self._RE_SUB_MAX_MATCHES):
            sufb, sufl = S.slice_(rb, rl, o, rl)
            mk, st_rel = nfa.match_start(sufb, sufl)
            mk = mk & active
            shb, shl = S.slice_(sufb, sufl, st_rel, sufl)
            am, susp, gs, ge = rx2.match(shb, shl)
            suspect = suspect | (mk & (susp | ~am))
            st_abs = o + st_rel
            en_abs = st_abs + ge[0]
            starts.append(jnp.where(mk, st_abs, 0).astype(jnp.int32))
            ends.append(jnp.where(mk, en_abs, 0).astype(jnp.int32))
            valids.append(mk)
            o = jnp.where(mk, en_abs, o)
            active = mk
        sufb, sufl = S.slice_(rb, rl, o, rl)
        suspect = suspect | (nfa.match(sufb, sufl) & active)
        self.raise_where(suspect, ExceptionCode.PYTHON_FALLBACK)
        fb, fl = S.splice_spans(rb, rl,
                                jnp.stack(starts, axis=1),
                                jnp.stack(ends, axis=1),
                                jnp.stack(valids, axis=1), new)
        return CV(t=T.STR, sbytes=fb, slen=fl)

    _SPLIT_INDEX_CAP = 32

    def _split_item(self, sv: CV, k: int) -> CV:
        """s.split(sep[, maxsplit])[k] — k-th piece via k unrolled finds
        (sep mode) or token-bound kernels (whitespace mode); rows with
        fewer pieces raise IndexError (python semantics)."""
        sb, sl = sv.sbytes, sv.slen
        sep, maxsplit = sv.names
        if k < 0:
            raise NotCompilable("split negative index")
        if maxsplit is not None and k > maxsplit:
            # len(result) <= maxsplit+1 always: IndexError on every row
            self.raise_where(jnp.ones(self.ctx.b, dtype=bool),
                             ExceptionCode.INDEXERROR)
            return CV(t=T.STR, sbytes=jnp.zeros_like(sb),
                      slen=jnp.zeros_like(sl))
        if sep is None:
            start, stop, missing = S.ws_token_bounds(sb, sl, k)
            if maxsplit is not None and k == maxsplit:
                # remainder piece: from token k's start to end of string
                stop = jnp.where(missing, stop, sl)
            self.raise_where(missing, ExceptionCode.INDEXERROR)
            fb, fl = S.slice_(sb, sl, start, stop)
            return CV(t=T.STR, sbytes=fb, slen=fl)
        m = len(sep)
        if k > self._SPLIT_INDEX_CAP:
            raise NotCompilable(f"split index {k} beyond unroll cap")
        start = jnp.zeros(self.ctx.b, dtype=jnp.int32)
        missing = jnp.zeros(self.ctx.b, dtype=bool)
        for _ in range(k):
            pos = S.find_const(sb, sl, sep, start=start)
            missing = missing | (pos < 0)
            start = jnp.where(pos < 0, start, pos + m)
        nxt = S.find_const(sb, sl, sep, start=start)
        stop = jnp.where(nxt < 0, sl, nxt)
        if maxsplit is not None and k == maxsplit:
            stop = sl   # remainder keeps later separators
        self.raise_where(missing, ExceptionCode.INDEXERROR)
        fb, fl = S.slice_(sb, sl, start, stop)
        return CV(t=T.STR, sbytes=fb, slen=fl)

    def _match_method(self, m: CV, attr: str, args: list[CV]) -> CV:
        if attr != "group":
            raise NotCompilable(f"match.{attr}")
        if len(args) == 0:
            idx = 0
        elif len(args) == 1 and args[0].is_const and \
                isinstance(args[0].const, int):
            idx = args[0].const
        else:
            raise NotCompilable("match.group with non-constant index")
        elts = m.elts
        if not elts and m.names and m.names[0] == "#lazy_groups":
            # unanchored two-pass: the anchored engine runs only here,
            # where groups are actually consumed (+ its fail-safe routing)
            elts, suspect = m.names[1]()
            self.raise_where(suspect, ExceptionCode.PYTHON_FALLBACK)
        if not 0 <= idx < len(elts):
            raise NotCompilable(f"no such regex group {idx}")
        # match is None -> .group raises AttributeError (python semantics)
        self.raise_where(~m.valid, ExceptionCode.ATTRIBUTEERROR)
        return elts[idx]

    def eval_JoinedStr(self, node: ast.JoinedStr) -> CV:
        parts: list[CV] = []
        for v in node.values:
            if isinstance(v, ast.Constant):
                parts.append(const_cv(v.value))
            elif isinstance(v, ast.FormattedValue):
                if v.conversion not in (-1, 115):
                    raise NotCompilable("f-string conversion")
                if v.format_spec is not None:
                    fs = v.format_spec
                    if not (isinstance(fs, ast.JoinedStr)
                            and all(isinstance(x, ast.Constant)
                                    for x in fs.values)):
                        raise NotCompilable("dynamic f-string format spec")
                    spec = "".join(str(x.value) for x in fs.values)
                    parts.append(self._format_method(
                        "{:" + spec + "}", [self.eval(v.value)]))
                else:
                    parts.append(self._to_str(self.eval(v.value)))
            else:
                raise NotCompilable("f-string part")
        out = parts[0] if parts else const_cv("")
        for p in parts[1:]:
            out = self._str_concat(out, p)
        return out

    # ===================================================================
    # helpers
    # ===================================================================
    def truthy(self, v: CV):
        if v.kind == "split":
            if v.names[0] is None:
                # whitespace mode CAN yield zero pieces ("".split() == [])
                return S.ws_token_count(v.sbytes, v.slen) > 0
            # sep mode always yields at least one piece
            return jnp.ones(self.ctx.b, dtype=bool)
        if v.kind == "match":
            # a match object is truthy exactly when the match exists (the
            # NFA path's groupless elts=() must not fall into the tuple
            # branch, where an empty tuple is constant-falsy)
            return v.valid
        if v.is_const:
            return jnp.full(self.ctx.b, bool(v.const), dtype=bool)
        base = v.base
        if base is T.NULL:
            return jnp.zeros(self.ctx.b, dtype=bool)
        if base is T.BOOL:
            tr = v.data
        elif base in (T.I64, T.F64):
            tr = v.data != 0
        elif base is T.STR:
            tr = v.slen > 0
        elif v.elts is not None:
            tr = jnp.full(self.ctx.b, len(v.elts) > 0, dtype=bool)
        else:
            raise NotCompilable(f"truthiness of {v.t}")
        if v.valid is not None:
            tr = tr & v.valid
        return tr

    def _require_numeric(self, v: CV, what: str) -> CV:
        v = self._unwrap_option(v, what)
        if v.t is T.NULL:
            # the TypeError is already flagged under the ACTIVE mask by
            # _unwrap_option; a typed dummy lets dead branches trace on
            # (e.g. `float(x) if x else d` over an all-null column)
            return CV(t=T.I64, data=jnp.zeros(self.ctx.b, dtype=jnp.int64))
        if v.is_const:
            if isinstance(v.const, (bool, int, float)):
                return materialize(v, self.ctx.b)
            raise NotCompilable(f"{what}: not numeric")
        if v.base not in (T.BOOL, T.I64, T.F64):
            raise NotCompilable(f"{what}: {v.t} not numeric")
        return v

    def _unwrap_option(self, v: CV, what: str) -> CV:
        """Using an Option value in a non-None-tolerant op raises TypeError
        for rows where it's None (Python: None + 1 -> TypeError)."""
        if v.t is T.NULL:  # incl. the literal None constant
            self.raise_where(jnp.ones(self.ctx.b, bool), ExceptionCode.TYPEERROR)
            return CV(t=T.NULL)  # non-const marker: callers emit typed dummies
        if v.valid is not None:
            self.raise_where(~v.valid, ExceptionCode.TYPEERROR)
            return CV(t=v.base, data=v.data, sbytes=v.sbytes, slen=v.slen,
                      elts=v.elts, names=v.names)
        return v

    def _as_i64(self, v: CV):
        if v.base is T.BOOL:
            return v.data.astype(jnp.int64)
        return v.data

    def _ascii_guard(self, sbytes, slen):
        """Index-space string ops count BYTES; multibyte UTF-8 rows diverge
        from Python codepoint semantics -> normal-case violation (row re-runs
        on the interpreter, keeping dual-mode exact)."""
        self.raise_where(S.non_ascii_rows(sbytes, slen),
                         ExceptionCode.NORMALCASEVIOLATION)

    # -- arithmetic ---------------------------------------------------------
    def _binop(self, op: ast.operator, a: CV, b: CV) -> CV:
        if a.is_const and b.is_const:
            try:
                return const_cv(_const_binop(op, a.const, b.const))
            except ZeroDivisionError:
                self.raise_where(jnp.ones(self.ctx.b, bool),
                                 ExceptionCode.ZERODIVISIONERROR)
                return const_cv(0)
        # string ops
        if a.base is T.STR or b.base is T.STR or \
                (a.is_const and isinstance(a.const, str)) or \
                (b.is_const and isinstance(b.const, str)):
            return self._str_binop(op, a, b)
        # keep exponent constness visible to _pow before materialization
        b_const_int = b.const if (b.is_const and isinstance(b.const, int)
                                  and not isinstance(b.const, bool)) else None
        a = self._require_numeric(a, "arithmetic")
        b = self._require_numeric(b, "arithmetic")
        if isinstance(op, ast.Pow) and b_const_int is not None:
            if b_const_int >= 0:
                return CV(t=T.I64 if a.base is not T.F64 else T.F64,
                          data=jnp.power(
                              self._as_i64(a) if a.base is not T.F64
                              else a.data, b_const_int))
            # int ** negative-const -> float in Python
            return CV(t=T.F64, data=jnp.power(self._cast(a.data, T.F64),
                                              float(b_const_int)))
        out_t = T.super_type(a.base, b.base)
        if out_t is T.BOOL:
            out_t = T.I64  # bool+bool -> int
        ad, bd = a.data, b.data
        if isinstance(op, ast.Add):
            return CV(t=out_t, data=self._cast(ad, out_t) + self._cast(bd, out_t))
        if isinstance(op, ast.Sub):
            return CV(t=out_t, data=self._cast(ad, out_t) - self._cast(bd, out_t))
        if isinstance(op, ast.Mult):
            return CV(t=out_t, data=self._cast(ad, out_t) * self._cast(bd, out_t))
        if isinstance(op, ast.Div):
            bz = self._cast(bd, T.F64)
            self.raise_where(bz == 0.0, ExceptionCode.ZERODIVISIONERROR)
            safe = jnp.where(bz == 0.0, 1.0, bz)
            return CV(t=T.F64, data=self._cast(ad, T.F64) / safe)
        if isinstance(op, ast.FloorDiv):
            return self._floordiv(a, b, out_t)
        if isinstance(op, ast.Mod):
            return self._mod(a, b, out_t)
        if isinstance(op, ast.Pow):
            return self._pow(a, b)
        if isinstance(op, ast.BitAnd) and out_t is T.I64:
            return CV(t=T.I64, data=self._cast(ad, T.I64) & self._cast(bd, T.I64))
        if isinstance(op, ast.BitOr) and out_t is T.I64:
            return CV(t=T.I64, data=self._cast(ad, T.I64) | self._cast(bd, T.I64))
        if isinstance(op, ast.BitXor) and out_t is T.I64:
            return CV(t=T.I64, data=self._cast(ad, T.I64) ^ self._cast(bd, T.I64))
        raise NotCompilable(f"operator {type(op).__name__}")

    def _cast(self, arr, t: T.Type):
        return arr.astype(dtype_for(t))

    def _floordiv(self, a: CV, b: CV, out_t: T.Type) -> CV:
        zero = self._cast(b.data, out_t) == 0
        self.raise_where(zero, ExceptionCode.ZERODIVISIONERROR)
        bd = jnp.where(zero, self._one(out_t), self._cast(b.data, out_t))
        ad = self._cast(a.data, out_t)
        return CV(t=out_t, data=jnp.floor_divide(ad, bd))

    def _mod(self, a: CV, b: CV, out_t: T.Type) -> CV:
        zero = self._cast(b.data, out_t) == 0
        self.raise_where(zero, ExceptionCode.ZERODIVISIONERROR)
        bd = jnp.where(zero, self._one(out_t), self._cast(b.data, out_t))
        ad = self._cast(a.data, out_t)
        return CV(t=out_t, data=jnp.mod(ad, bd))  # numpy mod == Python %

    def _one(self, t: T.Type):
        return jnp.asarray(1, dtype=dtype_for(t))

    def _pow(self, a: CV, b: CV) -> CV:
        if a.base is T.F64 or b.base is T.F64:
            return CV(t=T.F64,
                      data=jnp.power(self._cast(a.data, T.F64),
                                     self._cast(b.data, T.F64)))
        if b.is_const and isinstance(b.const, int):
            if b.const >= 0:
                return CV(t=T.I64, data=jnp.power(self._as_i64(a), b.const))
            # int ** negative-const -> float in Python
            return CV(t=T.F64, data=jnp.power(self._cast(a.data, T.F64),
                                              float(b.const)))
        bd = self._as_i64(b)
        neg = bd < 0
        # data-dependent result TYPE (int**neg -> float): those rows violate
        # the speculated normal case and re-run on the interpreter
        self.raise_where(neg, ExceptionCode.NORMALCASEVIOLATION)
        return CV(t=T.I64,
                  data=jnp.power(self._as_i64(a), jnp.where(neg, 0, bd)))

    # -- string ops ---------------------------------------------------------
    def _option_eq(self, a: CV, b: CV, raw_eq, op):
        """Validity-aware equality truth table: values equal AND both
        present, OR both None (Python: None == None)."""
        av = a.valid if a.valid is not None else jnp.ones(self.ctx.b, bool)
        bv = b.valid if b.valid is not None else jnp.ones(self.ctx.b, bool)
        a_null = a.t is T.NULL
        b_null = b.t is T.NULL
        if a_null:
            av = jnp.zeros(self.ctx.b, bool)
        if b_null:
            bv = jnp.zeros(self.ctx.b, bool)
        eq = (av & bv & raw_eq) | (~av & ~bv)
        return eq if isinstance(op, ast.Eq) else ~eq

    def _strip_option_strpair(self, v: CV):
        """(bytes, lens) of a possibly-Option str WITHOUT raising for None
        rows (callers gate on validity themselves)."""
        if v.is_const:
            if not isinstance(v.const, str):
                raise NotCompilable("expected str")
            return S.broadcast_const(v.const, self.ctx.b)
        if v.base is not T.STR:
            raise NotCompilable(f"expected str, got {v.t}")
        return v.sbytes, v.slen

    def _to_strpair(self, v: CV):
        """(bytes, lens) for a str CV (materializing consts)."""
        v = self._unwrap_option(v, "string op")
        if v.t is T.NULL:  # error already flagged under the active mask
            return S.broadcast_const("", self.ctx.b)
        return self._strip_option_strpair(v)

    def _str_binop(self, op: ast.operator, a: CV, b: CV) -> CV:
        if isinstance(op, ast.Add):
            return self._str_concat(a, b)
        if isinstance(op, ast.Mod):
            return self._str_format(a, b)
        if isinstance(op, ast.Mult):
            sv, iv = (a, b) if (a.base is T.STR or (
                a.is_const and isinstance(a.const, str))) else (b, a)
            if not (iv.is_const and isinstance(iv.const, int)
                    and not isinstance(iv.const, bool)):
                raise NotCompilable("str * dynamic int")
            n = max(0, iv.const)
            if sv.is_const:
                return const_cv(sv.const * n)
            if n == 0:
                return const_cv("")
            # repeated doubling: O(log n) concats instead of n-1 chained
            # kernels with quadratically growing intermediates
            pows = {1: sv}
            p2 = 1
            while p2 * 2 <= n:
                pows[p2 * 2] = self._str_concat(pows[p2], pows[p2])
                p2 *= 2
            out = None
            rem = n
            for k in sorted(pows, reverse=True):
                while rem >= k:
                    out = pows[k] if out is None else \
                        self._str_concat(out, pows[k])
                    rem -= k
            return out
        raise NotCompilable(f"str operator {type(op).__name__}")

    def _str_concat(self, a: CV, b: CV) -> CV:
        if a.is_const and b.is_const:
            return const_cv(a.const + b.const)
        ab, al = self._to_strpair(a)
        bb, bl = self._to_strpair(b)
        rb, rl = S.concat(ab, al, bb, bl)
        return CV(t=T.STR, sbytes=rb, slen=rl)

    def _str_format(self, fmt: CV, args: CV) -> CV:
        """'%05d' % x — constant format string, limited directives."""
        if not (fmt.is_const and isinstance(fmt.const, str)):
            raise NotCompilable("dynamic format string")
        spec = fmt.const
        arg_list = list(args.elts) if args.elts is not None else [args]
        import re as _re

        # '%%' splits out first so "%%d" stays the literal '%d' instead of
        # consuming an argument (advisor finding, round 1 — CPython treats
        # '%%' as an escape wherever it appears)
        pieces = _re.split(r"(%%|%0?\d*(?:\.\d+)?[dsfxXo])", spec)
        out: Optional[CV] = None
        ai = 0
        for piece in pieces:
            if not piece:
                continue
            if piece == "%%":
                part = const_cv("%")
            elif _re.fullmatch(r"%0?\d*(?:\.\d+)?[dsfxXo]", piece):
                if ai >= len(arg_list):
                    raise NotCompilable("format arity")
                arg = arg_list[ai]
                ai += 1
                kind = piece[-1]
                pad_zero = piece.startswith("%0")
                body = piece[1:-1]
                prec = None
                if "." in body:
                    body, ps_ = body.split(".", 1)
                    prec = int(ps_ or "0")
                width = int(body.lstrip("0") or "0") if body else 0
                if kind == "f":
                    part = self._float_format(arg, 6 if prec is None
                                              else prec, width, pad_zero)
                    out = part if out is None else \
                        self._str_concat(out, part)
                    continue
                if prec is not None:
                    raise NotCompilable(f"format {piece!r}")
                if kind in ("x", "X", "o"):
                    if arg.base is T.F64 or (arg.is_const and
                                             isinstance(arg.const, float)):
                        raise NotCompilable("%x of float")  # TypeError
                    base = 8 if kind == "o" else 16
                    fb, fl = S.int_to_base(self._as_i64(
                        self._require_numeric(arg, "%x")), base,
                        prefix=False)
                    if kind == "X":
                        fb, fl = S.upper(fb, fl)
                    if pad_zero and width > 0:
                        fb, fl = S.zfill(fb, fl, width)
                    elif width > 0:
                        fb, fl = S.pad_left(fb, fl, width, " ")
                    part = CV(t=T.STR, sbytes=fb, slen=fl)
                    out = part if out is None else \
                        self._str_concat(out, part)
                    continue
                if kind == "d":
                    arg = self._require_numeric(arg, "%d")
                    fb, fl = S.format_i64(self._as_i64(arg), width=width,
                                          pad_zero=pad_zero)
                    if width > 0 and not pad_zero:
                        fb, fl = S.pad_left(fb, fl, width, " ")
                    part = CV(t=T.STR, sbytes=fb, slen=fl)
                elif kind == "s":
                    part = self._to_str(arg)
                    if width > 0:
                        pb, pl = self._to_strpair(part)
                        fb, fl = S.pad_left(pb, pl, width, " ")
                        part = CV(t=T.STR, sbytes=fb, slen=fl)
                else:
                    raise NotCompilable(f"format kind {kind!r}")
            else:
                if "%" in piece:
                    # an unrecognized directive (%#x, %e, %-8d, lone %)
                    # must never pass through as literal text
                    raise NotCompilable(f"format {piece!r}")
                part = const_cv(piece)
            out = part if out is None else self._str_concat(out, part)
        if ai != len(arg_list):
            # CPython: TypeError('not all arguments converted ...') — the
            # interpreter keeps exact semantics
            raise NotCompilable("surplus % format arguments")
        return out if out is not None else const_cv("")

    def _format_method(self, spec: str, args: list[CV]) -> CV:
        """'...{}...{:02}...'.format(a, b) with plain / zero-pad int specs
        (reference: FunctionRegistry str.format subset). Anything outside the
        supported subset raises NotCompilable so rows keep exact Python
        semantics via the interpreter."""
        import re as _re

        pieces = _re.split(r"(\{\{|\}\}|\{[^{}]*\})", spec)
        out: Optional[CV] = None
        auto_i = 0
        saw_auto = saw_manual = False
        for piece in pieces:
            if not piece:
                continue
            if piece == "{{":
                part = const_cv("{")
            elif piece == "}}":
                part = const_cv("}")
            elif piece.startswith("{"):
                m = _re.fullmatch(
                    r"\{(\d*)(?::([+]?)(0?)(\d*)(,?)(?:\.(\d+))?"
                    r"([dsf]?))?\}", piece)
                if not m:
                    raise NotCompilable(f"format spec {piece!r}")
                if m.group(1):
                    saw_manual = True
                    idx = int(m.group(1))
                else:
                    saw_auto = True
                    idx = auto_i
                    auto_i += 1
                if saw_auto and saw_manual:
                    # CPython raises ValueError on mixed numbering
                    raise NotCompilable("mixed manual/auto format numbering")
                if idx >= len(args):
                    raise NotCompilable("format arity")
                arg = args[idx]
                plus = m.group(2) == "+"
                zero = m.group(3) == "0"
                width = int(m.group(4)) if m.group(4) else 0
                comma = m.group(5) == ","
                prec = int(m.group(6)) if m.group(6) else None
                kind = m.group(7) or ""
                if comma and (prec is not None or kind not in ("", "d")):
                    raise NotCompilable(f"format spec {piece!r}")
                if comma and zero:
                    # python zero-fills WITH commas ('0,012'): beyond the
                    # grouping kernel
                    raise NotCompilable("comma grouping with zero fill")
                if kind == "f":
                    part = self._float_format(arg, 6 if prec is None
                                              else prec, width, zero,
                                              plus=plus)
                    out = part if out is None else \
                        self._str_concat(out, part)
                    continue
                if prec is not None:
                    # bare '{:.2}' is CPython general format (g-style
                    # sig-digits; ValueError on ints) — not fixed-point
                    raise NotCompilable(f"format spec {piece!r}")
                arg_is_float = arg.base is T.F64 or (
                    arg.is_const and isinstance(arg.const, float))
                if (kind == "d" or comma) and arg_is_float:
                    # CPython: ValueError for :d; ',' on floats groups the
                    # int part (beyond the kernel) — both fall back
                    raise NotCompilable("format d/comma of float")
                is_int = (kind == "d") or (
                    kind == "" and ((arg.base is T.I64 and not arg.is_const)
                                    or (arg.is_const and
                                        isinstance(arg.const, int) and
                                        not isinstance(arg.const, bool))))
                if is_int:
                    na = self._require_numeric(arg, "format int")
                    iv = self._as_i64(na)
                    if plus:
                        # sign first, THEN zero-fill to the total width
                        # (python counts the sign inside the field)
                        fb, fl = self._prepend_plus(*S.format_i64(iv),
                                                    iv >= 0)
                        if zero and width > 0:
                            fb, fl = S.zfill(fb, fl, width)
                    else:
                        fb, fl = S.format_i64(iv, width=0 if comma
                                              else width, pad_zero=zero)
                    if comma:
                        fb, fl = S.group_thousands(fb, fl)
                    if width > 0 and not zero:
                        fb, fl = S.pad_left(fb, fl, width, " ")
                    part = CV(t=T.STR, sbytes=fb, slen=fl)
                elif kind == "d":
                    raise NotCompilable("format d of non-int")
                elif plus or comma:
                    # CPython: ValueError for sign/comma on non-numerics
                    raise NotCompilable("sign/comma flag on non-numeric")
                else:
                    part = self._to_str(arg)
                    if width > 0:
                        # Python left-aligns strings; zero flag fills right
                        pb, pl = self._to_strpair(part)
                        fb, fl = S.pad_right(pb, pl, width,
                                             "0" if zero else " ")
                        part = CV(t=T.STR, sbytes=fb, slen=fl)
            else:
                if "{" in piece or "}" in piece:
                    # CPython raises ValueError on single braces
                    raise NotCompilable("single brace in format string")
                part = const_cv(piece)
            out = part if out is None else self._str_concat(out, part)
        return out if out is not None else const_cv("")

    def _prepend_plus(self, fb, fl, nonneg):
        """'+' before non-negative rows (negatives already carry '-')."""
        pb, pl = S.broadcast_const("+", self.ctx.b)
        return S.concat(pb, jnp.where(nonneg, pl, 0), fb, fl)

    def _float_format(self, arg: CV, prec: int, width: int = 0,
                      pad_zero: bool = False, plus: bool = False) -> CV:
        """%.Nf / {:.Nf} fixed-point rendering; rounding ties and huge
        magnitudes route to the interpreter (CPython renders from the
        exact binary value — scaled integer math can double-round)."""
        from ..core.errors import ExceptionCode

        na = self._require_numeric(arg, "float format")
        fv = self._cast(na.data, T.F64)
        fb, fl, suspect = S.format_f64(fv, prec)
        self.raise_where(suspect, ExceptionCode.NORMALCASEVIOLATION)
        if plus:
            fb, fl = self._prepend_plus(fb, fl, ~jnp.signbit(fv))
        if width > 0:
            if pad_zero:
                fb, fl = S.zfill(fb, fl, width)
            else:
                fb, fl = S.pad_left(fb, fl, width, " ")
        return CV(t=T.STR, sbytes=fb, slen=fl)

    def _to_str(self, v: CV) -> CV:
        if v.is_const:
            return const_cv(str(v.const))
        if v.base is T.STR:
            return v
        if v.base is T.BOOL:
            v2 = self._require_numeric(v, "str()")
            tb, tl = S.broadcast_const("True", self.ctx.b)
            fb2, fl2 = S.broadcast_const("False", self.ctx.b)
            tb, fb2 = S._pad_common(tb, fb2)
            sb = jnp.where(v2.data[:, None], tb, fb2)
            sl = jnp.where(v2.data, tl, fl2)
            return CV(t=T.STR, sbytes=sb.astype(jnp.uint8),
                      slen=sl.astype(jnp.int32))
        if v.base is T.I64:
            v = self._require_numeric(v, "str()")
            fb, fl = S.format_i64(self._as_i64(v))
            return CV(t=T.STR, sbytes=fb, slen=fl)
        raise NotCompilable(f"str() of {v.t}")

    def _slice(self, val: CV, sl: ast.Slice) -> CV:
        if val.base is not T.STR:
            if val.elts is not None:
                # tuple slicing with const bounds
                lo = self._const_or_none(sl.lower)
                hi = self._const_or_none(sl.upper)
                if sl.step is not None:
                    raise NotCompilable("tuple slice step")
                return tuple_cv(list(val.elts)[slice(lo, hi)],
                                kind=val.kind)
            raise NotCompilable(f"slice of {val.t}")
        if sl.step is not None:
            raise NotCompilable("string slice step")
        val = self._unwrap_option(val, "slice")
        self._ascii_guard(val.sbytes, val.slen)
        start = self._index_arr(sl.lower)
        stop = self._index_arr(sl.upper)
        rb, rl = S.slice_(val.sbytes, val.slen, start, stop)
        return CV(t=T.STR, sbytes=rb, slen=rl)

    def _const_or_none(self, node):
        if node is None:
            return None
        v = self.eval(node)
        if v.is_const and isinstance(v.const, int):
            return v.const
        raise NotCompilable("non-constant tuple slice bound")

    def _index_arr(self, node):
        if node is None:
            return None
        v = self._require_numeric(self.eval(node), "slice bound")
        return self._as_i64(v).astype(jnp.int32)

    def _str_method(self, recv: CV, name: str, args: list[CV]) -> CV:
        if recv.is_const and all(a.is_const for a in args):
            try:
                return const_cv(getattr(recv.const, name)(
                    *[a.const for a in args]))
            except Exception:
                pass
        recv = self._unwrap_option(recv, f"str.{name}")
        rb, rl = self._to_strpair(recv)

        def need_const_str(i: int) -> str:
            if i >= len(args) or not (args[i].is_const and
                                      isinstance(args[i].const, str)):
                raise NotCompilable(f"str.{name}: needs constant str arg")
            return args[i].const

        if name == "casefold":
            # ASCII casefold == lower; multibyte rows already routed by the
            # guard below where byte semantics could diverge
            self._ascii_guard(rb, rl)
            fb, fl = S.lower(rb, rl)
            return CV(t=T.STR, sbytes=fb, slen=fl)
        if name in ("removeprefix", "removesuffix"):
            affix = need_const_str(0)
            if not affix:
                return CV(t=T.STR, sbytes=rb, slen=rl)
            m = len(affix.encode())
            if name == "removeprefix":
                hit = S.startswith_const(rb, rl, affix)
                start = jnp.where(hit, m, 0).astype(jnp.int32)
                fb, fl = S.slice_(rb, rl, start, None)
            else:
                hit = S.endswith_const(rb, rl, affix)
                stop = jnp.where(hit, rl - m, rl).astype(jnp.int32)
                fb, fl = S.slice_(rb, rl, None, stop)
            return CV(t=T.STR, sbytes=fb, slen=fl)
        if name in ("partition", "rpartition"):
            self._ascii_guard(rb, rl)
            sep = need_const_str(0)
            if not sep:
                raise NotCompilable("partition with empty separator")
            m = len(sep.encode())
            pos = S.find_const(rb, rl, sep, reverse=name == "rpartition")
            found = pos >= 0
            if name == "partition":
                # not found: (s, '', '')
                head_stop = jnp.where(found, pos, rl).astype(jnp.int32)
                tail_start = jnp.where(found, pos + m, rl).astype(jnp.int32)
            else:
                # not found: ('', '', s)
                head_stop = jnp.where(found, pos, 0).astype(jnp.int32)
                tail_start = jnp.where(found, pos + m,
                                       jnp.zeros_like(rl)).astype(jnp.int32)
            hb, hl = S.slice_(rb, rl, None, head_stop)
            sb2, sl2 = S.broadcast_const(sep, self.ctx.b)
            sl2 = jnp.where(found, sl2, 0)
            tb, tl = S.slice_(rb, rl, tail_start, None)
            return tuple_cv([CV(t=T.STR, sbytes=hb, slen=hl),
                             CV(t=T.STR, sbytes=sb2, slen=sl2),
                             CV(t=T.STR, sbytes=tb, slen=tl)])
        if name in ("lower", "upper", "swapcase"):
            # byte-level case maps cover ASCII only: 'équipe'.upper() must
            # route, not return 'éQUIPE' (review r4)
            self._ascii_guard(rb, rl)
            fb, fl = getattr(S, name)(rb, rl)
            return CV(t=T.STR, sbytes=fb, slen=fl)
        if name in ("strip", "lstrip", "rstrip"):
            self._ascii_guard(rb, rl)  # unicode whitespace divergence
            chars = need_const_str(0) if args else None
            left = name != "rstrip"
            right = name != "lstrip"
            fb, fl = S.strip(rb, rl, chars, left=left, right=right)
            return CV(t=T.STR, sbytes=fb, slen=fl)
        if name in ("find", "rfind", "index", "rindex"):
            self._ascii_guard(rb, rl)  # positions are byte offsets
            needle = need_const_str(0)
            start = None
            if len(args) > 1:
                start = self._as_i64(
                    self._require_numeric(args[1], "find start")
                ).astype(jnp.int32)
            pos = S.find_const(rb, rl, needle, start=start,
                               reverse=name.startswith("r"))
            if name in ("index", "rindex"):
                self.raise_where(pos < 0, ExceptionCode.VALUEERROR)
            return CV(t=T.I64, data=pos.astype(jnp.int64))
        if name == "replace":
            old = need_const_str(0)
            new = need_const_str(1)
            fb, fl = S.replace_const(rb, rl, old, new)
            return CV(t=T.STR, sbytes=fb, slen=fl)
        if name == "startswith":
            return CV(t=T.BOOL, data=S.startswith_const(rb, rl, need_const_str(0)))
        if name == "endswith":
            return CV(t=T.BOOL, data=S.endswith_const(rb, rl, need_const_str(0)))
        if name == "count":
            self._ascii_guard(rb, rl)
            needle = need_const_str(0)
            cnt = S.count_const(rb, rl, needle)
            return CV(t=T.I64, data=cnt.astype(jnp.int64))
        if name in ("isdigit", "isdecimal", "isnumeric", "isalpha",
                    "isalnum", "isspace"):
            self._ascii_guard(rb, rl)
            return CV(t=T.BOOL, data=S.char_class_all(rb, rl, name))
        if name in ("islower", "isupper", "istitle"):
            self._ascii_guard(rb, rl)
            return CV(t=T.BOOL, data=S.case_pred(rb, rl, name))
        if name == "capitalize":
            self._ascii_guard(rb, rl)
            fb, fl = S.capitalize(rb, rl)
            return CV(t=T.STR, sbytes=fb, slen=fl)
        if name == "title":
            self._ascii_guard(rb, rl)
            fb, fl = S.title(rb, rl)
            return CV(t=T.STR, sbytes=fb, slen=fl)
        if name == "format":
            if not (recv.is_const and isinstance(recv.const, str)):
                raise NotCompilable("format on dynamic string")
            return self._format_method(recv.const, args)
        if name == "split":
            self._ascii_guard(rb, rl)
            if len(args) > 2:
                raise NotCompilable("str.split arity")
            maxsplit = None
            if len(args) == 2:
                if not (args[1].is_const and isinstance(args[1].const, int)):
                    raise NotCompilable("str.split dynamic maxsplit")
                maxsplit = args[1].const if args[1].const >= 0 else None
            if not args or (args[0].is_const and args[0].const is None):
                sep = None     # whitespace mode: runs of ws, ends stripped
            else:
                sep = need_const_str(0)
                if sep == "":
                    raise NotCompilable("str.split empty separator")
            # LAZY view (reference: split codegen'd lazily too,
            # FunctionRegistry): only [const_int] and len() force pieces —
            # the result's ARITY is data-dependent, so it can't be a tuple
            return CV(t=T.PYOBJECT, kind="split", names=(sep, maxsplit),
                      sbytes=rb, slen=rl)
        if name == "join":
            if not (recv.is_const and isinstance(recv.const, str)):
                raise NotCompilable("join with dynamic separator")
            if len(args) != 1:
                raise NotCompilable("join takes exactly one argument")
            items = self._cv_iter_items(args[0])
            if items is None:
                raise NotCompilable("join over non-static iterable")
            out: Optional[CV] = None
            sep_cv = const_cv(recv.const)
            for it in items:
                if not (it.base is T.STR or
                        (it.is_const and isinstance(it.const, str))):
                    raise NotCompilable("join of non-str element")
                out = it if out is None else self._str_concat(
                    self._str_concat(out, sep_cv), it)
            return out if out is not None else const_cv("")
        if name in ("center", "ljust", "rjust"):
            # width semantics are per CHARACTER: multibyte rows must take
            # the interpreter path like the other byte-position methods
            self._ascii_guard(rb, rl)
            if not (args and args[0].is_const
                    and isinstance(args[0].const, int)):
                raise NotCompilable(f"str.{name} dynamic width")
            fill = " "
            if len(args) > 1:
                if not (args[1].is_const and isinstance(args[1].const, str)
                        and len(args[1].const.encode()) == 1):
                    raise NotCompilable(f"str.{name} fill char")
                fill = args[1].const
            kern = {"center": S.center, "ljust": S.pad_right,
                    "rjust": S.pad_left}[name]
            fb, fl = kern(rb, rl, args[0].const, fill)
            return CV(t=T.STR, sbytes=fb, slen=fl)
        if name == "zfill":
            if not (args and args[0].is_const and isinstance(args[0].const, int)):
                raise NotCompilable("str.zfill dynamic width")
            fb, fl = S.zfill(rb, rl, args[0].const)
            return CV(t=T.STR, sbytes=fb, slen=fl)
        raise NotCompilable(f"str.{name}")

    # -- dict methods (named-row CVs; reference: FunctionRegistry dict
    # pop/popitem codegen) --------------------------------------------------
    def _dict_method(self, node, recv: CV, name: str, args: list[CV]) -> CV:
        keys = list(recv.names or ())
        if name == "get":
            if not (args and args[0].is_const
                    and isinstance(args[0].const, str)):
                raise NotCompilable("dict.get dynamic key")
            if args[0].const in keys:
                return recv.elts[keys.index(args[0].const)]
            return args[1] if len(args) > 1 else const_cv(None)
        if name == "keys":
            return tuple_cv([const_cv(k) for k in keys])
        if name == "values":
            return tuple_cv(list(recv.elts))
        if name == "items":
            return tuple_cv([tuple_cv([const_cv(k), v])
                             for k, v in zip(keys, recv.elts)])
        if name in ("pop", "popitem"):
            if name == "pop":
                if not (args and args[0].is_const
                        and isinstance(args[0].const, str)):
                    raise NotCompilable("dict.pop dynamic key")
                key = args[0].const
                if key not in keys:
                    if len(args) > 1:
                        return args[1]
                    raise NotCompilable(f"dict.pop missing key {key!r}")
                idx = keys.index(key)
                ret: CV = recv.elts[idx]
            else:
                if args:
                    raise NotCompilable("dict.popitem arity")
                if not keys:
                    raise NotCompilable("popitem on empty dict")
                idx = len(keys) - 1
                ret = tuple_cv([const_cv(keys[idx]), recv.elts[idx]])
            rest = tuple_cv([e for j, e in enumerate(recv.elts) if j != idx],
                            names=[k for j, k in enumerate(keys) if j != idx])
            # mutation is only sound on receivers we can fully account for:
            # a plain un-aliased name (rebind) or a fresh temporary whose
            # value nothing else can observe. Anything else (subscript/
            # attribute receivers, aliased names) must fall back, or the
            # dropped mutation silently diverges from CPython
            tgt = node.func.value
            if isinstance(tgt, ast.Name):
                if self._name_escapes(tgt.id):
                    raise NotCompilable(f"dict.{name} on aliased dict")
                if tgt.id in self.env:
                    self._assign_target(tgt, rest)
            elif not isinstance(tgt, (ast.Dict, ast.DictComp, ast.Call)):
                raise NotCompilable(f"dict.{name} on non-name receiver")
            return ret
        raise NotCompilable(f"dict.{name}")

    def _name_escapes(self, name: str) -> bool:
        """Conservative alias analysis over the UDF AST: may `name`'s value
        be observable through ANOTHER binding? True for any bare-Name read
        that isn't the receiver of a subscript/attribute access — e.g.
        `e = d`, `(d,)`, `f(d)`, `return d`. Mutating through the name is
        only sound when it never escapes (value-semantics env can't model
        shared mutation)."""
        tree = getattr(self, "udf_tree", None)
        if tree is None:
            return True   # no tree to analyze: assume the worst
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Name) and node.id == name
                    and isinstance(node.ctx, ast.Load)):
                continue
            parent = getattr(node, "_tpx_parent", None)
            if parent is None:
                # annotate lazily once per tree
                for p in ast.walk(tree):
                    for ch in ast.iter_child_nodes(p):
                        ch._tpx_parent = p  # type: ignore[attr-defined]
                parent = getattr(node, "_tpx_parent", None)
            if isinstance(parent, (ast.Subscript, ast.Attribute)) and \
                    parent.value is node:
                continue   # d[...] / d.method(...): receiver use, no escape
            return True
        return False

    # -- comparisons --------------------------------------------------------
    def _compare(self, op: ast.cmpop, a: CV, b: CV):
        # None comparisons: x is None / x == None
        if isinstance(op, (ast.Is, ast.IsNot, ast.Eq, ast.NotEq)):
            a_is_none = (a.t is T.NULL) or (a.is_const and a.const is None)
            b_is_none = (b.t is T.NULL) or (b.is_const and b.const is None)
            if a_is_none or b_is_none:
                other = b if a_is_none else a
                if a_is_none and b_is_none:
                    isn = jnp.ones(self.ctx.b, dtype=bool)
                elif other.valid is not None:
                    isn = ~other.valid
                elif other.t is T.NULL:
                    isn = jnp.ones(self.ctx.b, dtype=bool)
                else:
                    isn = jnp.zeros(self.ctx.b, dtype=bool)
                pos = isinstance(op, (ast.Is, ast.Eq))
                return isn if pos else ~isn
        if isinstance(op, (ast.In, ast.NotIn)):
            res = self._contains(a, b)
            return res if isinstance(op, ast.In) else ~res
        # strings
        a_str = a.base is T.STR or (a.is_const and isinstance(a.const, str))
        b_str = b.base is T.STR or (b.is_const and isinstance(b.const, str))
        if a_str and b_str:
            if isinstance(op, (ast.Eq, ast.NotEq)) and \
                    (a.valid is not None or b.valid is not None):
                # Python: None == "x" is False (no TypeError) — keep Option
                # rows on device instead of erroring them to the interpreter
                ab, al = self._strip_option_strpair(a)
                bb, bl = self._strip_option_strpair(b)
                return self._option_eq(a, b, S.equals(ab, al, bb, bl), op)
            ab, al = self._to_strpair(a)
            bb, bl = self._to_strpair(b)
            if isinstance(op, ast.Eq):
                return S.equals(ab, al, bb, bl)
            if isinstance(op, ast.NotEq):
                return ~S.equals(ab, al, bb, bl)
            if isinstance(op, ast.Lt):
                return S.compare_lt(ab, al, bb, bl)
            if isinstance(op, ast.LtE):
                return S.compare_lt(ab, al, bb, bl, or_equal=True)
            if isinstance(op, ast.Gt):
                return S.compare_lt(bb, bl, ab, al)
            if isinstance(op, ast.GtE):
                return S.compare_lt(bb, bl, ab, al, or_equal=True)
            raise NotCompilable("string comparison op")
        if a_str != b_str:
            # str vs non-str: values never equal, but None == None is True
            # when both sides are Option/None
            if isinstance(op, (ast.Eq, ast.NotEq)):
                return self._option_eq(a, b,
                                       jnp.zeros(self.ctx.b, dtype=bool), op)
            self.raise_where(jnp.ones(self.ctx.b, bool), ExceptionCode.TYPEERROR)
            return jnp.zeros(self.ctx.b, dtype=bool)
        if isinstance(op, (ast.Eq, ast.NotEq)) and \
                (a.valid is not None or b.valid is not None):
            a2 = CV(t=a.base, data=a.data) if a.valid is not None else a
            b2 = CV(t=b.base, data=b.data) if b.valid is not None else b
            an = self._require_numeric(a2, "comparison")
            bn = self._require_numeric(b2, "comparison")
            return self._option_eq(a, b, an.data == bn.data, op)
        an = self._require_numeric(a, "comparison")
        bn = self._require_numeric(b, "comparison")
        ad, bd = an.data, bn.data
        if isinstance(op, ast.Eq):
            return ad == bd
        if isinstance(op, ast.NotEq):
            return ad != bd
        if isinstance(op, ast.Lt):
            return ad < bd
        if isinstance(op, ast.LtE):
            return ad <= bd
        if isinstance(op, ast.Gt):
            return ad > bd
        if isinstance(op, ast.GtE):
            return ad >= bd
        raise NotCompilable(f"comparison {type(op).__name__}")

    def _contains(self, needle: CV, hay: CV):
        # 'x' in s  (constant needle, columnar haystack)
        if hay.base is T.STR or (hay.is_const and isinstance(hay.const, str)):
            if needle.is_const and isinstance(needle.const, str):
                hb, hl = self._to_strpair(hay)
                return S.contains_const(hb, hl, needle.const)
            raise NotCompilable("dynamic needle for `in`")
        items = None
        if hay.is_const and isinstance(hay.const,
                                       (tuple, list, set, frozenset, dict)):
            # iteration order gives dict KEYS — python `in` semantics
            items = [const_cv(v) for v in hay.const]
        elif hay.elts is not None:
            # dict CV: python `in` tests KEYS (which are static strs)
            items = [const_cv(k) for k in hay.names] \
                if hay.names is not None else list(hay.elts)
        if items is not None:
            acc = jnp.zeros(self.ctx.b, dtype=bool)
            for e in items:
                acc = acc | self._compare(ast.Eq(), needle, e)
            return acc
        raise NotCompilable(f"`in` over {hay.t}")

    # -- builtins -----------------------------------------------------------
    def _builtin_int(self, args: list[CV]) -> CV:
        if not args:
            return const_cv(0)
        v = args[0]
        if len(args) > 1:
            if not (args[1].is_const and isinstance(args[1].const, int)
                    and 2 <= args[1].const <= 36):
                raise NotCompilable("int(x, base) dynamic base")
            if not (v.base is T.STR or (v.is_const and
                                        isinstance(v.const, str))):
                raise NotCompilable("int(x, base) of non-string")
            if v.is_const:
                try:
                    return const_cv(int(v.const, args[1].const))
                except ValueError:
                    pass   # every row raises: keep python semantics below
            rb, rl = self._to_strpair(v)
            self._ascii_guard(rb, rl)
            val, bad, ovf = S.parse_int_base(rb, rl, args[1].const)
            self.raise_where(bad, ExceptionCode.VALUEERROR)
            self.raise_where(ovf & ~bad, ExceptionCode.NORMALCASEVIOLATION)
            return CV(t=T.I64, data=val)
        if v.is_const:
            try:
                return const_cv(int(v.const))
            except (ValueError, TypeError):
                pass
        v = self._unwrap_option(v, "int()")
        if v.t is T.NULL:
            return CV(t=T.I64, data=jnp.zeros(self.ctx.b, dtype=jnp.int64))
        if v.base is T.STR:
            val, bad, route = S.parse_i64(v.sbytes, v.slen)
            self.raise_where(bad, ExceptionCode.VALUEERROR)
            # valid python int, unrepresentable in i64: interpreter row
            self.raise_where(route, ExceptionCode.NORMALCASEVIOLATION)
            return CV(t=T.I64, data=val)
        if v.base is T.F64:
            return CV(t=T.I64, data=jnp.trunc(v.data).astype(jnp.int64))
        if v.base in (T.I64, T.BOOL):
            return CV(t=T.I64, data=self._as_i64(v))
        raise NotCompilable(f"int() of {v.t}")

    def _builtin_float(self, args: list[CV]) -> CV:
        if not args:
            return const_cv(0.0)
        v = args[0]
        if v.is_const:
            try:
                return const_cv(float(v.const))
            except (ValueError, TypeError):
                pass
        v = self._unwrap_option(v, "float()")
        if v.t is T.NULL:  # error already flagged; dummy keeps tracing
            return CV(t=T.F64, data=jnp.zeros(self.ctx.b, dtype=jnp.float64))
        if v.base is T.STR:
            val, bad, route = S.parse_f64(v.sbytes, v.slen)
            self.raise_where(bad, ExceptionCode.VALUEERROR)
            # inf/nan literals parse fine in CPython: interpreter row
            self.raise_where(route, ExceptionCode.NORMALCASEVIOLATION)
            return CV(t=T.F64, data=val)
        if v.base in (T.I64, T.BOOL, T.F64):
            return CV(t=T.F64, data=self._cast(
                v.data if v.base is not T.BOOL else v.data.astype(jnp.int64),
                T.F64))
        raise NotCompilable(f"float() of {v.t}")

    def _builtin_str(self, args: list[CV]) -> CV:
        if not args:
            return const_cv("")
        return self._to_str(args[0])

    def _builtin_bool(self, args: list[CV]) -> CV:
        if not args:
            return const_cv(False)
        return CV(t=T.BOOL, data=self.truthy(args[0]))

    def _builtin_len(self, args: list[CV]) -> CV:
        if args and args[0].kind == "split":
            sv = args[0]
            sep, maxsplit = sv.names
            if sep is None:
                cnt = S.ws_token_count(sv.sbytes, sv.slen)
            else:
                cnt = S.count_const(sv.sbytes, sv.slen, sep) \
                    .astype(jnp.int64) + 1
            if maxsplit is not None:
                cnt = jnp.minimum(cnt, maxsplit + 1)
            return CV(t=T.I64, data=cnt.astype(jnp.int64))
        v = args[0]
        if v.is_const:
            try:
                return const_cv(len(v.const))
            except TypeError:
                pass  # e.g. None: falls through to the unwrap error path
        if v.elts is not None:
            return const_cv(len(v.elts))
        v = self._unwrap_option(v, "len()")
        if v.t is T.NULL:
            return CV(t=T.I64, data=jnp.zeros(self.ctx.b, dtype=jnp.int64))
        if v.base is T.STR:
            self._ascii_guard(v.sbytes, v.slen)
            return CV(t=T.I64, data=v.slen.astype(jnp.int64))
        raise NotCompilable(f"len() of {v.t}")

    def _builtin_abs(self, args: list[CV]) -> CV:
        v = self._require_numeric(args[0], "abs()")
        return CV(t=v.base if v.base is not T.BOOL else T.I64,
                  data=jnp.abs(self._as_i64(v) if v.base is T.BOOL else v.data))

    def _builtin_round(self, args: list[CV]) -> CV:
        v = self._require_numeric(args[0], "round()")
        nd = 0
        if len(args) > 1:
            if not (args[1].is_const and isinstance(args[1].const, int)):
                raise NotCompilable("round() dynamic ndigits")
            nd = args[1].const
        if v.base in (T.I64, T.BOOL):
            return CV(t=T.I64, data=self._as_i64(v))
        scaled = v.data * (10.0 ** nd)
        r = jnp.round(scaled)  # banker's rounding — matches Python round()
        if len(args) > 1:
            return CV(t=T.F64, data=r / (10.0 ** nd))
        return CV(t=T.I64, data=r.astype(jnp.int64))

    def _tuple_method(self, recv: CV, name: str, args: list[CV]) -> CV:
        """tuple.index / tuple.count over static elements (unrolled
        equality tests; index raises ValueError rows when absent)."""
        if len(args) != 1:
            raise NotCompilable(f"tuple.{name} arity")
        needle = args[0]
        eqs = [self._compare(ast.Eq(), needle, e) for e in recv.elts]
        if name == "count":
            cnt = jnp.zeros(self.ctx.b, dtype=jnp.int64)
            for eq in eqs:
                cnt = cnt + eq.astype(jnp.int64)
            return CV(t=T.I64, data=cnt)
        idx = jnp.full(self.ctx.b, -1, dtype=jnp.int64)
        for i in range(len(eqs) - 1, -1, -1):
            idx = jnp.where(eqs[i], i, idx)
        self.raise_where(idx < 0, ExceptionCode.VALUEERROR)
        return CV(t=T.I64, data=jnp.maximum(idx, 0))

    def _int_to_base(self, args: list[CV], base: int, what: str) -> CV:
        if len(args) != 1:
            raise NotCompilable(f"{what} arity")
        v = args[0]
        if not (v.base is T.I64 or v.base is T.BOOL or
                (v.is_const and isinstance(v.const, int))):
            raise NotCompilable(f"{what} of non-int")   # python: TypeError
        if v.is_const:
            # const fold (also: arbitrary-precision consts never reach the
            # i64 kernel)
            return const_cv({16: hex, 8: oct, 2: bin}[base](v.const))
        fb, fl = S.int_to_base(self._as_i64(
            self._require_numeric(v, what)), base)
        return CV(t=T.STR, sbytes=fb, slen=fl)

    def _builtin_hex(self, args: list[CV]) -> CV:
        return self._int_to_base(args, 16, "hex")

    def _builtin_oct(self, args: list[CV]) -> CV:
        return self._int_to_base(args, 8, "oct")

    def _builtin_bin(self, args: list[CV]) -> CV:
        return self._int_to_base(args, 2, "bin")

    def _builtin_divmod(self, args: list[CV]) -> CV:
        if len(args) != 2:
            raise NotCompilable("divmod arity")
        return tuple_cv([self._binop(ast.FloorDiv(), args[0], args[1]),
                         self._binop(ast.Mod(), args[0], args[1])])

    def _builtin_ord(self, args: list[CV]) -> CV:
        if len(args) != 1:
            raise NotCompilable("ord arity")
        v = args[0]
        if v.is_const and isinstance(v.const, str):
            if len(v.const) != 1:
                raise NotCompilable("ord of non-1-char constant")
            return const_cv(ord(v.const))
        rb, rl = self._to_strpair(v)
        self._ascii_guard(rb, rl)
        # TypeError rows where len != 1 (python raises TypeError)
        self.raise_where(rl != 1, ExceptionCode.TYPEERROR)
        return CV(t=T.I64, data=rb[:, 0].astype(jnp.int64))

    def _builtin_chr(self, args: list[CV]) -> CV:
        if len(args) != 1:
            raise NotCompilable("chr arity")
        v = self._require_numeric(args[0], "chr")
        if v.base is T.F64 or (v.is_const and isinstance(v.const, float)):
            raise NotCompilable("chr of float")   # python: TypeError
        code = self._as_i64(v)
        # ValueError outside unicode range; non-ASCII routes (byte matrix
        # is utf-8; multibyte encoding of one codepoint stays interpreter)
        self.raise_where((code < 0) | (code > 0x10FFFF),
                         ExceptionCode.VALUEERROR)
        self.raise_where(code > 127, ExceptionCode.NORMALCASEVIOLATION)
        b = jnp.clip(code, 0, 127).astype(jnp.uint8)[:, None]
        return CV(t=T.STR, sbytes=b, slen=jnp.ones(self.ctx.b,
                                                   dtype=jnp.int32))

    def _builtin_iter(self, args: list[CV]) -> CV:
        """iter(x) with STATIC consumption: each next() call site advances
        a trace-time cursor (reference: IteratorContextProxy.cc's iterator
        state machines; the per-call-site cursor is the vectorized analog
        for straight-line consumption)."""
        if len(args) != 1:
            raise NotCompilable("iter arity")
        v = args[0]
        cell = {"pos": 0}
        items = self._cv_iter_items(v)
        if items is not None:
            return CV(t=T.PYOBJECT, kind="iter",
                      names=("#static", tuple(items), cell))
        if v.kind == "split":
            cnt, item_at, _ = self._split_dynamic(v)
            return CV(t=T.PYOBJECT, kind="iter",
                      names=("#dyn", (cnt, item_at), cell))
        raise NotCompilable("iter over unsupported value")

    def _builtin_next(self, args: list[CV]) -> CV:
        if len(args) not in (1, 2):
            raise NotCompilable("next arity")
        it = args[0]
        if it.kind != "iter":
            raise NotCompilable("next over non-iterator")
        # consumption must be uniform across rows: under an if-branch mask,
        # after a possible early return, or inside a loop with per-row
        # exit/break masks, the trace-time cursor would advance for rows
        # python skips (review r4: `if a == 'x': next(it)` silently
        # misaligned the cursor) -> interpreter
        if self.mask is not None or self.ret_val is not None:
            raise NotCompilable("next under row-divergent control flow")
        if any(lp.get("dyn") or lp["brk"] is not None
               or lp["cont"] is not None for lp in self.loops):
            raise NotCompilable("next under row-divergent control flow")
        tag, src, cell = it.names
        k = cell["pos"]
        cell["pos"] = k + 1
        default = args[1] if len(args) == 2 else None
        if tag == "#static":
            if k < len(src):
                return src[k]
            if default is None:
                self.raise_where(jnp.ones(self.ctx.b, dtype=bool),
                                 ExceptionCode.STOPITERATION)
                return const_cv(None)
            return default
        cnt, item_at = src
        if k >= _DYN_ITER_CAP:
            raise NotCompilable("next past dynamic iterator cap")
        has_k = cnt > k
        val = item_at(k)
        if default is None:
            self.raise_where(~has_k, ExceptionCode.STOPITERATION)
            return val
        return merge_cv(self, has_k, val, default)

    def _builtin_sorted(self, args: list[CV]) -> CV:
        """sorted() over a static iterable via a compare-exchange network
        (vectorized bubble network: k(k-1)/2 predicated swaps — data-
        dependent orderings can't reorder a traced program, so every lane
        carries its own permutation through merge_cv)."""
        if len(args) != 1:
            raise NotCompilable("sorted arity")
        items = self._cv_iter_items(args[0])
        if items is None:
            raise NotCompilable("sorted over non-static iterable")
        vals = list(items)
        k = len(vals)
        if k > 8:
            raise NotCompilable("sorted over >8 elements")
        for i in range(k):
            for j in range(k - 1 - i):
                lt = self._compare(ast.Lt(), vals[j + 1], vals[j])
                a, b = vals[j], vals[j + 1]
                vals[j] = merge_cv(self, lt, b, a)
                vals[j + 1] = merge_cv(self, lt, a, b)
        return tuple_cv(vals, kind="list")

    def _unroll_width(self, count, bound) -> int:
        """Masked-unroll width for a runtime-length iterable: the static
        bound when one exists, else the cap — rows iterating past it raise
        LOOPCAPEXCEEDED and resolve exactly on the interpreter. Shared by
        dynamic for-loops and genexp reductions."""
        width = _DYN_ITER_CAP if bound is None else min(bound,
                                                        _DYN_ITER_CAP)
        if bound is None or bound > _DYN_ITER_CAP:
            self.raise_where(count > width, ExceptionCode.LOOPCAPEXCEEDED)
        return width

    def _dyn_genexp_steps(self, v: CV):
        """Iterate a dyngen CV (lazy genexp over a runtime-length iterable,
        _comprehension): yields (value CV, active-mask) per unrolled step,
        with loop masks arranged so element-expression errors raise only
        for rows still iterating AND passing the filters (reference:
        IteratorContextProxy-driven reductions). Element expressions
        evaluate under the genexp's DEFINING env; a second consumption
        refuses to compile (python generators exhaust — re-tracing would
        double-count)."""
        node, (count, item_at, bound), def_env, cell = v.names
        if cell["consumed"]:
            raise NotCompilable("generator consumed twice")
        cell["consumed"] = True
        gen = node.generators[0]
        width = self._unroll_width(count, bound)
        saved = self.env
        self.env = dict(def_env)
        lp = {"brk": None, "cont": None, "done": None, "dyn": True}
        self.loops.append(lp)
        steps = []
        try:
            for k in range(width):
                lp["done"] = count <= k
                lp["cont"] = None
                self._assign_target(gen.target, item_at(k))
                mask = count > k
                for cond_node in gen.ifs:
                    ctr = self.truthy(self.eval(cond_node))
                    mask = mask & ctr
                    # rows failing the filter skip the element expression
                    # (its errors must not fire for them)
                    drop = self.active() & ~ctr
                    lp["cont"] = drop if lp["cont"] is None \
                        else lp["cont"] | drop
                val = self.eval(node.elt)
                steps.append((val, mask))
        finally:
            self.loops.pop()
            self.env = saved
        return steps

    def _builtin_sum(self, args: list[CV]) -> CV:
        if len(args) not in (1, 2):
            raise NotCompilable("sum() arity")
        start: CV = args[1] if len(args) == 2 else const_cv(0)
        if start.base is T.STR or (start.is_const
                                   and isinstance(start.const, str)):
            # python forbids sum() over strings (TypeError): the
            # interpreter path reproduces the exact error — applies to the
            # dyngen branch too (review r4: it silently concatenated)
            raise NotCompilable("sum() can't sum strings")
        if args[0].kind == "dyngen":
            steps = self._dyn_genexp_steps(args[0])
            acc = start
            for val, mask in steps:
                acc = merge_cv(self, mask,
                               self._binop(ast.Add(), acc, val), acc)
            return acc
        items = self._cv_iter_items(args[0])
        if items is None:
            raise NotCompilable("sum over non-static iterable")
        acc = start
        for it in items:
            acc = self._binop(ast.Add(), acc, it)
        return acc

    def _builtin_any(self, args: list[CV]) -> CV:
        return self._any_all(args, any_mode=True)

    def _builtin_all(self, args: list[CV]) -> CV:
        return self._any_all(args, any_mode=False)

    def _any_all(self, args: list[CV], any_mode: bool) -> CV:
        if len(args) != 1:
            raise NotCompilable("any/all arity")
        if args[0].kind == "dyngen":
            steps = self._dyn_genexp_steps(args[0])
            acc = jnp.full(self.ctx.b, not any_mode, dtype=bool)
            for val, mask in steps:
                t = self.truthy(val)
                acc = (acc | (mask & t)) if any_mode \
                    else (acc & (~mask | t))
            return CV(t=T.BOOL, data=acc)
        items = self._cv_iter_items(args[0])
        if items is None:
            raise NotCompilable("any/all over non-static iterable")
        if all(it.is_const for it in items):
            # const-fold so while/comprehension conditions stay trace-static
            vals = [it.const for it in items]
            return const_cv(any(vals) if any_mode else all(vals))
        acc = self.truthy(items[0])
        for it in items[1:]:
            tr = self.truthy(it)
            acc = (acc | tr) if any_mode else (acc & tr)
        return CV(t=T.BOOL, data=acc)

    def _builtin_min(self, args: list[CV]) -> CV:
        return self._minmax(args, jnp.minimum)

    def _builtin_max(self, args: list[CV]) -> CV:
        return self._minmax(args, jnp.maximum)

    def _minmax(self, args: list[CV], fn) -> CV:
        if len(args) == 1 and args[0].kind == "dyngen":
            steps = self._dyn_genexp_steps(args[0])
            want_min = fn is jnp.minimum
            acc: Optional[CV] = None
            seen = jnp.zeros(self.ctx.b, dtype=bool)
            for val, mask in steps:
                if acc is None:
                    acc, seen = val, mask
                    continue
                res = self._compare(ast.Lt() if want_min else ast.Gt(),
                                    val, acc)
                cmp = self.truthy(res) if isinstance(res, CV) else res
                acc = merge_cv(self, mask & (~seen | cmp), val, acc)
                seen = seen | mask
            if acc is None:     # zero-width unroll: every row is empty
                self.raise_where(jnp.ones(self.ctx.b, dtype=bool),
                                 ExceptionCode.VALUEERROR)
                return const_cv(None)
            # python: min()/max() of an EMPTY iterable raises ValueError
            self.raise_where(~seen, ExceptionCode.VALUEERROR)
            return acc
        if len(args) == 1:
            items = self._cv_iter_items(args[0])
            if not items:
                raise NotCompilable("min/max over non-static iterable")
            args = items
        if any(a.base is T.STR or (a.is_const and isinstance(a.const, str))
               for a in args):
            want_min = fn is jnp.minimum
            out = args[0]
            for b in args[1:]:
                lt = self._compare(ast.Lt(), b, out)   # raw [B] bool
                out = merge_cv(self, lt if want_min else ~lt, b, out)
            return out
        vs = [self._require_numeric(a, "min/max") for a in args]
        out_t = vs[0].base
        for v in vs[1:]:
            out_t = T.super_type(out_t, v.base)
        acc = self._cast(vs[0].data, out_t)
        for v in vs[1:]:
            acc = fn(acc, self._cast(v.data, out_t))
        return CV(t=out_t, data=acc)

    # -- math module --------------------------------------------------------
    _MATH_UNARY = {
        "floor": (jnp.floor, T.I64), "ceil": (jnp.ceil, T.I64),
        "sqrt": (jnp.sqrt, T.F64), "sin": (jnp.sin, T.F64),
        "cos": (jnp.cos, T.F64), "tan": (jnp.tan, T.F64),
        "exp": (jnp.exp, T.F64), "log": (jnp.log, T.F64),
        "log2": (jnp.log2, T.F64), "log10": (jnp.log10, T.F64),
        "fabs": (jnp.abs, T.F64), "trunc": (jnp.trunc, T.I64),
        "radians": (jnp.radians, T.F64), "degrees": (jnp.degrees, T.F64),
        "isnan": (jnp.isnan, T.BOOL), "isinf": (jnp.isinf, T.BOOL),
        "atan": (jnp.arctan, T.F64), "asin": (jnp.arcsin, T.F64),
        "acos": (jnp.arccos, T.F64), "sinh": (jnp.sinh, T.F64),
        "cosh": (jnp.cosh, T.F64), "tanh": (jnp.tanh, T.F64),
        "expm1": (jnp.expm1, T.F64), "log1p": (jnp.log1p, T.F64),
    }

    def _module_fn(self, fn, args: list[CV]) -> CV:
        mod = getattr(fn, "__module__", None)
        name = getattr(fn, "__name__", None)
        if mod == "math" and name in self._MATH_UNARY:
            jfn, out_t = self._MATH_UNARY[name]
            v = self._require_numeric(args[0], f"math.{name}")
            res = jfn(self._cast(v.data, T.F64))
            if out_t is T.I64:
                return CV(t=T.I64, data=res.astype(jnp.int64))
            if out_t is T.BOOL:
                return CV(t=T.BOOL, data=res)
            return CV(t=T.F64, data=res)
        if mod == "string" and name == "capwords":
            rb, rl = self._to_strpair(args[0])
            self._ascii_guard(rb, rl)  # unicode whitespace divergence
            fb, fl = S.capwords(rb, rl)
            return CV(t=T.STR, sbytes=fb, slen=fl)
        if mod == "math" and name in self._MATH_BINARY:
            jfn = self._MATH_BINARY[name]
            a = self._require_numeric(args[0], f"math.{name}")
            b = self._require_numeric(args[1], f"math.{name}")
            bd = self._cast(b.data, T.F64)
            if name == "fmod":
                # math.fmod(x, 0.0) raises ValueError in CPython; jnp.fmod
                # would silently emit NaN
                self.raise_where(bd == 0.0, ExceptionCode.VALUEERROR)
            return CV(t=T.F64, data=jfn(self._cast(a.data, T.F64), bd))
        if mod == "math" and name == "isclose":
            if len(args) != 2:
                raise NotCompilable("math.isclose arity")
            a = self._cast(self._require_numeric(args[0], "isclose").data,
                           T.F64)
            c = self._cast(self._require_numeric(args[1], "isclose").data,
                           T.F64)
            tol = 1e-09 * jnp.maximum(jnp.abs(a), jnp.abs(c))
            # CPython order: a == b short-circuits True (equal infinities
            # are close); any remaining infinity is False (the formula's
            # inf tolerance would otherwise accept everything)
            finite = ~(jnp.isinf(a) | jnp.isinf(c))
            return CV(t=T.BOOL,
                      data=(a == c) | (finite & (jnp.abs(a - c) <= tol)))
        raise NotCompilable(f"module fn {mod}.{name}")

    _MATH_BINARY = {
        "pow": jnp.power, "fmod": jnp.fmod, "hypot": jnp.hypot,
        "copysign": jnp.copysign, "atan2": jnp.arctan2,
    }


# ---------------------------------------------------------------------------
# CV merging (predicated phi nodes)
# ---------------------------------------------------------------------------

def merge_cv(frame: Frame, mask, a: CV, b: CV) -> CV:
    """where(mask, a, b) over CVs, unifying types (the phi node of the
    predicated control flow; reference analog: TypeAnnotator's if-branch
    type unification)."""
    b_ = frame.ctx.b
    if a.is_const and b.is_const and a.const == b.const and \
            type(a.const) is type(b.const):
        return a
    # None joins: produce Option
    a_null = a.t is T.NULL
    b_null = b.t is T.NULL
    if a_null and b_null:
        return null_cv()
    if a_null or b_null:
        other = b if a_null else a
        other_m = materialize(other, b_) if other.is_const else other
        ov = other_m.valid if other_m.valid is not None \
            else jnp.ones(b_, dtype=bool)
        # valid exactly where the non-null side is selected and itself valid
        sel_other = ~mask if a_null else mask
        new_valid = sel_other & ov
        return CV(t=T.option(other_m.base), data=other_m.data,
                  valid=new_valid, sbytes=other_m.sbytes, slen=other_m.slen,
                  elts=other_m.elts, names=other_m.names)
    am = materialize(a, b_) if a.is_const else a
    bm = materialize(b, b_) if b.is_const else b
    # tuples
    if am.elts is not None and bm.elts is not None:
        if len(am.elts) != len(bm.elts):
            raise NotCompilable("merging tuples of different arity")
        if am.kind != bm.kind:   # list vs tuple branches: per-row TYPE
            raise NotCompilable("merging list and tuple")
        elts = tuple(merge_cv(frame, mask, x, y)
                     for x, y in zip(am.elts, bm.elts))
        valid = None
        if am.valid is not None or bm.valid is not None:
            av = am.valid if am.valid is not None else jnp.ones(b_, bool)
            bv = bm.valid if bm.valid is not None else jnp.ones(b_, bool)
            valid = jnp.where(mask, av, bv)
        return tuple_cv(elts, names=am.names or bm.names, valid=valid,
                        kind=am.kind)
    at, bt = am.base, bm.base
    # strings
    if at is T.STR and bt is T.STR:
        ab, al = am.sbytes, am.slen
        bb2, bl = bm.sbytes, bm.slen
        ab, bb2 = S._pad_common(ab, bb2)
        sb = jnp.where(mask[:, None], ab, bb2)
        sl = jnp.where(mask, al, bl)
        valid = _merge_valid(mask, am, bm, b_)
        t = T.option(T.STR) if valid is not None else T.STR
        return CV(t=t, sbytes=sb, slen=sl, valid=valid)
    # numerics
    if at.is_numeric() and bt.is_numeric():
        out_t = T.super_type(at, bt)
        data = jnp.where(mask,
                         am.data.astype(dtype_for(out_t)),
                         bm.data.astype(dtype_for(out_t)))
        valid = _merge_valid(mask, am, bm, b_)
        t = T.option(out_t) if valid is not None else out_t
        return CV(t=t, data=data, valid=valid)
    raise NotCompilable(f"cannot merge {a.t} and {b.t}")


def _merge_valid(mask, am: CV, bm: CV, b_: int):
    if am.valid is None and bm.valid is None:
        return None
    av = am.valid if am.valid is not None else jnp.ones(b_, dtype=bool)
    bv = bm.valid if bm.valid is not None else jnp.ones(b_, dtype=bool)
    return jnp.where(mask, av, bv)


def _const_binop(op: ast.operator, a, b):
    import operator as _op

    table = {
        ast.Add: _op.add, ast.Sub: _op.sub, ast.Mult: _op.mul,
        ast.Div: _op.truediv, ast.FloorDiv: _op.floordiv, ast.Mod: _op.mod,
        ast.Pow: _op.pow, ast.BitAnd: _op.and_, ast.BitOr: _op.or_,
        ast.BitXor: _op.xor, ast.LShift: _op.lshift, ast.RShift: _op.rshift,
    }
    fn = table.get(type(op))
    if fn is None:
        raise NotCompilable(f"const op {type(op).__name__}")
    return fn(a, b)


def _class_run_table(pattern: str):
    """[256] bool table when `pattern` is exactly one character class
    repeated 1+ times ('[0-9]+', '\\s+', 'x+', '[^a-z]+'); else None."""
    import re as _pyre

    try:
        from re import _parser as _sre
    except ImportError:                      # pragma: no cover - py<3.11
        import sre_parse as _sre             # type: ignore

    import numpy as np

    from ..ops.regex import _byte_in_spec, _in_spec

    if any(ord(c) > 127 for c in pattern):
        return None
    try:
        tree = _sre.parse(pattern)
    except Exception:
        return None
    if tree.state.flags & ~_pyre.UNICODE.value:
        return None
    terms = list(tree)
    if len(terms) != 1:
        return None
    op, av = terms[0]
    # exactly class+ / literal+ — a bare class (no repeat) replaces EACH
    # char, and {2,} must not match length-1 runs: both diverge from the
    # run-collapsing kernel, so only MAX_REPEAT(1, MAXREPEAT) qualifies
    if str(op) != "MAX_REPEAT":
        return None
    lo, hi, body = av
    if lo != 1 or str(hi) != "MAXREPEAT" or len(body) != 1:
        return None
    op, av = list(body)[0]
    spec = None
    if str(op) == "IN":
        spec = _in_spec(av)
    elif str(op) == "LITERAL":
        spec = (("lit", av),)
    if spec is None:
        return None
    tab = np.zeros(256, dtype=bool)
    for c in range(256):
        if _byte_in_spec(c, spec):
            tab[c] = True
    return tab
