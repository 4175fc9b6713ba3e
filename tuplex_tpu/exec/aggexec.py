"""Aggregate stage execution: vectorized folds + segment reductions.

The device path exploits the associative-combine contract the reference
imposes on user aggregates (reference: AggregateFunctions.cc agg_combine_f
is required to be associative for thread-parallel aggregation;
LocalBackend.cc:2219 createFinalHashmap merges per-task tables). Here:

  per-partition: recognized fold exprs evaluate as whole columns on device
  (Emitter trace) and reduce via jnp.sum / segment_sum — per-device partials
  then combine on host (tiny), or via psum over a mesh (parallel backend).

Rows that error during expr evaluation (plus boxed fallback rows) fold on the
interpreter exactly like other dual-mode work.
"""

from __future__ import annotations

import time
from typing import Any, Optional

import numpy as np

from ..compiler.emitter import EmitCtx, Emitter, Frame
from ..core import typesys as T
from ..core.errors import NotCompilable
from ..core.row import Row
from ..plan import aggregates as A
from ..plan import logical as L
from ..runtime import columns as C
from ..runtime import tracing as TR
from .local import ExceptionRecord


def _aot(fn, role: str, op, schema):
    """Content-addressed compile for the scan-fold executables (the agg
    analog of the stage fns' compilequeue route: identical fold structures
    across jobs/processes reuse one executable). The HLO module reads
    `jit_tpx_<role>_<key8>`, keyed by the operator's identity (UDF
    sources) and the input schema."""
    from ..plan.physical import _op_identity
    from .compilequeue import aot_jit

    return aot_jit(TR.name_fn(fn, role,
                              TR.key8(_op_identity(op), schema.name)),
                   tag="agg")


from ..parallel.collectives import reduce_identity as _identity


def _combine_scalar(reducer: str, a, b):
    if reducer == "sum":
        return a + b
    if reducer == "min":
        return min(a, b)
    return max(a, b)


class AggregateExecutor:
    def __init__(self, backend):
        self.backend = backend

    # ==================================================================
    def execute(self, stage, partitions: list[C.Partition]):
        from .local import StageResult

        op = stage.op
        t0 = time.perf_counter()
        with TR.span("agg:execute", "exec") as _sp:
            _sp.set("op", type(op).__name__)
            if isinstance(op, A.UniqueOperator):
                parts, excs = self._unique(op, partitions)
            elif isinstance(op, A.AggregateByKeyOperator):
                parts, excs = self._aggregate(op, partitions, by_key=True)
            elif isinstance(op, A.AggregateOperator):
                parts, excs = self._aggregate(op, partitions, by_key=False)
            else:
                raise NotCompilable(f"aggregate stage op {op!r}")
            rows_out = sum(p.num_rows for p in parts)
            _sp.set("rows_out", rows_out)
        from . import compilequeue as _cq

        cs, cn = _cq.consume_tag("agg")
        m = {"wall_s": time.perf_counter() - t0,
             "rows_out": rows_out,
             "exception_rows": len(excs),
             "compile_s": cs, "stage_compiles": cn}
        return StageResult(parts, excs, m)

    # ==================================================================
    def _unique(self, op, partitions):
        """Distinct rows, first-occurrence order. Vectorized per partition
        via structured-view np.unique; cross-partition merge via host set."""
        seen_sig: set = set()
        seen_val: set = set()
        out_rows: list = []
        for part in partitions:
            self.backend.mm.touch(part)
            sig = _row_signatures(part)
            for i in range(part.num_rows):
                s = sig[i] if sig is not None and i not in part.fallback \
                    else None
                if s is not None and s in seen_sig:
                    continue
                row = part.decode_row(i)
                try:
                    key = tuple(row.values)
                except TypeError:
                    out_rows.append(row)  # unhashable: keep (reference keeps
                    continue              # such rows in the backup dict)
                if s is not None:
                    seen_sig.add(s)
                if key in seen_val:
                    continue
                seen_val.add(key)
                out_rows.append(row)
        schema = op.schema()
        values = [r.unwrap() if len(schema.columns) == 1 else tuple(r.values)
                  for r in out_rows]
        if not values:
            return [], []
        return [C.build_partition(values, schema)], []

    # ==================================================================
    def _aggregate(self, op, partitions, by_key: bool):
        spec = A.recognize_fold(op.aggregate_udf)
        excs: list[ExceptionRecord] = []
        ps = partitions[0].schema if partitions else None

        if by_key:
            kidx = [ps.columns.index(c) for c in op.key_columns] if ps else []
            groups: dict = {}
            scan_k = None
            if spec is None and ps is not None and not getattr(
                    self.backend, "interpret_only", False):
                scan_k = A.ScanFold.try_build(op, ps)
            for part in partitions:
                self.backend.mm.touch(part)
                device_ok = spec is not None and self._device_fold_bykey(
                    op, spec, part, kidx, groups, excs)
                if not device_ok and scan_k is not None:
                    device_ok = self._scan_fold_bykey(op, scan_k, part, kidx,
                                                      groups, excs)
                if not device_ok:
                    with TR.span("agg:host-merge", "exec") as _sp:
                        _sp.set("rows", part.num_rows).set("path", "python")
                        self._python_fold(op, part, range(part.num_rows),
                                          groups, kidx, excs)
            with TR.span("agg:host-merge", "exec") as _sp:
                _sp.set("groups", len(groups)).set("path", "output")
                out_schema = op.schema()
                values = []
                for k, acc in groups.items():
                    accs = acc if isinstance(acc, tuple) else (acc,)
                    values.append(tuple(k) + tuple(accs))
                if not values:
                    return [], excs
                return [C.build_partition(values, out_schema)], excs

        # whole-dataset aggregate: pattern folds vectorize; everything else
        # tries the compiled sequential scan fold before per-row python
        scan = None
        if spec is None and ps is not None and not getattr(
                self.backend, "interpret_only", False):
            scan = A.ScanFold.try_build(op, ps)
        if scan is not None:
            return self._scan_aggregate(op, scan, partitions, excs)
        acc_holder = {"acc": op.initial, "started": False}

        def merge_partial(partial):
            # partial is a raw reduction (identity-seeded); merge via the
            # recognized reducers
            accs = list(acc_holder["acc"]) if isinstance(
                acc_holder["acc"], tuple) else [acc_holder["acc"]]
            parts_ = list(partial) if isinstance(partial, tuple) else [partial]
            merged = [_combine_scalar(r, a, p)
                      for r, a, p in zip(spec.reducers, accs, parts_)]
            acc_holder["acc"] = tuple(merged) if isinstance(
                acc_holder["acc"], tuple) else merged[0]

        groups2: dict = {(): op.initial}
        for part in partitions:
            self.backend.mm.touch(part)
            done = False
            if spec is not None:
                partial, bad_rows = self._device_fold(op, spec, part)
                if partial is not None:
                    with TR.span("agg:host-merge", "exec") as _sp:
                        _sp.set("rows", len(bad_rows)).set("groups", 1)
                        merge_partial(partial)
                        self._python_fold(op, part, bad_rows, groups2, [],
                                          excs, into_key=())
                    done = True
            if not done:
                with TR.span("agg:host-merge", "exec") as _sp:
                    _sp.set("rows", part.num_rows).set("path", "python")
                    self._python_fold(op, part, range(part.num_rows),
                                      groups2, [], excs, into_key=())
        # fold the python-side accumulator into the device-side one via the
        # user combine (both are real agg values, reference: agg_combine_f)
        py_acc = groups2[()]
        if spec is not None:
            if py_acc != op.initial:
                acc_holder["acc"] = op.combine_udf.func(
                    acc_holder["acc"], py_acc)
            final = acc_holder["acc"]
        else:
            final = py_acc
        schema = op.schema()
        return [C.build_partition([final], schema)], excs

    # ------------------------------------------------------------------
    def _scan_aggregate(self, op, scan, partitions, excs):
        """Arbitrary aggregate UDF on device: lax.scan fold per partition
        with the accumulator CHAINED partition-to-partition (the initial
        value seeds exactly once, matching the interpreter tier); rows the
        scan flags bad fold onto the running value via the interpreter
        (reference: per-task agg_agg_f, AggregateFunctions.cc:16-178)."""
        import jax
        import numpy as np

        acc_val = op.initial

        def fold_py(part, indices):
            nonlocal acc_val
            g = {(): acc_val}
            self._python_fold(op, part, indices, g, [], excs, into_key=())
            acc_val = g[()]

        for part in partitions:
            self.backend.mm.touch(part)
            outs = None
            if part.n_normal() > 0:
                try:
                    with TR.span("agg:segment-fold", "exec") as _sp:
                        _sp.set("rows", part.num_rows).set("groups", 1)
                        fn = self.backend.jit_cache.get_or_build(
                            ("scanfold", op.id, part.schema.name),
                            lambda: _aot(scan.build_fn(), "aggscan", op,
                                         part.schema))
                        batch = C.stage_partition(part,
                                                  self.backend.bucket_mode)
                        acc_in = scan.encode_acc(acc_val)
                        outs = jax.device_get(fn(batch.arrays, acc_in))
                except Exception as e:
                    from ..utils.logging import get_logger

                    get_logger("exec").warning(
                        "scan fold failed (%s: %s); partition folds on the "
                        "interpreter", type(e).__name__, e)
            with TR.span("agg:host-merge", "exec") as _sp:
                _sp.set("rows", part.num_rows)
                if outs is None:
                    fold_py(part, range(part.num_rows))
                    continue
                *acc_leaves, bads = outs
                acc_val = scan.decode_acc(acc_leaves)
                bad_idx = np.nonzero(np.asarray(bads)[:part.num_rows])[0]
                if len(bad_idx):
                    fold_py(part, bad_idx.tolist())
        schema = op.schema()
        return [C.build_partition([acc_val], schema)], excs

    # ------------------------------------------------------------------
    def _scan_fold_bykey(self, op, scan, part, kidx, groups, excs) -> bool:
        """Arbitrary aggregateByKey UDF on device: segmented lax.scan fold —
        per-key accumulator slots seeded from the running `groups` table so
        cross-partition chaining (and the once-per-key initial) stays exact;
        rows the scan flags bad fold via the interpreter afterward."""
        import jax

        real = _real_mask(part)
        n = part.num_rows
        with TR.span("agg:factorize-keys", "exec") as _sp:
            _sp.set("rows", n)
            codes, uniq_rows = _factorize_keys(part, kidx, real)
            if codes is None or len(uniq_rows) == 0:
                return False
            nseg = len(uniq_rows)
            _sp.set("groups", nseg)
            nseg_b = C.bucket_size(nseg)
            # key columns only: a device-resident (lazy) partition must not
            # be forced to host just to name its groups
            keys = C.decode_key_tuples(part, uniq_rows.tolist(), kidx)
        try:
            seg_init = A._scanfold_encode_segments(
                scan, [groups.get(k, op.initial) for k in keys], nseg_b)
        except Exception:
            return False   # an existing acc no longer conforms: python path
        try:
            with TR.span("agg:segment-fold", "exec") as _sp:
                _sp.set("rows", n).set("groups", nseg)
                fn = self.backend.jit_cache.get_or_build(
                    ("scanfoldseg", op.id, part.schema.name),
                    lambda: _aot(A._seg_build_fn(scan), "aggfold", op,
                                 part.schema))
                batch = C.stage_partition(part, self.backend.bucket_mode)
                b = batch.arrays["#rowvalid"].shape[0]
                codes_b = np.full(b, nseg_b, dtype=np.int32)
                codes_b[:n][real] = codes
                outs = jax.device_get(fn(batch.arrays, codes_b, seg_init))
        except Exception as e:
            from ..utils.logging import get_logger

            get_logger("exec").warning(
                "segmented scan fold failed (%s: %s); partition folds on "
                "the interpreter", type(e).__name__, e)
            return False
        with TR.span("agg:host-merge", "exec") as _sp:
            _sp.set("rows", n).set("groups", nseg)
            *leaves, bads = outs
            bads_n = np.asarray(bads)[:n]
            # ghost-group guard (matches the mesh fold's counts check): a
            # key whose rows ALL errored must not emit an initial-only
            # output row
            ok_codes = codes_b[:n][~bads_n]
            seg_ok = np.bincount(ok_codes, minlength=nseg_b + 1)
            vals = A._scanfold_decode_segments(scan, leaves, nseg)
            for si, k in enumerate(keys):
                if seg_ok[si] or k in groups:
                    groups[k] = vals[si]
            bad_idx = np.nonzero(bads_n)[0].tolist()
            if bad_idx:
                self._python_fold(op, part, bad_idx, groups, kidx, excs)
        return True

    # ------------------------------------------------------------------
    def _python_fold(self, op, part, indices, groups, kidx, excs,
                     into_key: Optional[tuple] = None):
        for i in indices:
            row = part.decode_row(i)
            k = into_key if into_key is not None else \
                tuple(row.values[j] for j in kidx)
            acc = groups.get(k, op.initial)
            try:
                groups[k] = A._apply_agg(op.aggregate_udf, acc, row)
            except Exception as e:
                excs.append(ExceptionRecord(op.id, type(e).__name__,
                                            row.unwrap()))

    # ------------------------------------------------------------------
    def _device_fold(self, op, spec: A.FoldSpec, part: C.Partition):
        """(partial_tuple|scalar, bad_row_indices) or (None, _) if the
        partition can't run on device."""
        fp = getattr(part, "fold_partials", None)
        if fp is not None and fp[0] == op.id:
            # the transform stage already computed identity-seeded partials
            # inside its own device pass (plan_stages fused the fold) — no
            # second staging/dispatch needed
            partials, bad = fp[1], fp[2]
            out = tuple(partials) if not spec.scalar else partials[0]
            return out, list(bad)
        mesh = getattr(self.backend, "mesh", None)
        if mesh is not None:
            try:
                return self._device_fold_mesh(op, spec, part, mesh)
            except NotCompilable:
                return None, range(part.num_rows)
        try:
            with TR.span("agg:eval-exprs", "exec") as _sp:
                _sp.set("rows", part.num_rows)
                vals, ok_mask, err = self._eval_exprs(op, spec, part)
        except NotCompilable:
            return None, range(part.num_rows)
        import jax.numpy as jnp

        partials = []
        with TR.span("agg:segment-fold", "exec") as _sp:
            _sp.set("rows", part.num_rows).set("groups", 1)
            for cv_data, reducer in zip(vals, spec.reducers):
                is_float = cv_data.dtype.kind == "f"
                ident = _identity(reducer, is_float)
                masked = jnp.where(ok_mask, cv_data, ident)
                if reducer == "sum":
                    r = masked.sum()
                elif reducer == "min":
                    r = masked.min()
                else:
                    r = masked.max()
                partials.append(r.item())
        bad = np.nonzero(~np.asarray(ok_mask)[: part.num_rows] &
                         _real_mask(part))[0].tolist()
        bad += [i for i in part.fallback if i not in bad]
        out = tuple(partials) if not spec.scalar else partials[0]
        return out, sorted(set(bad))

    def _device_fold_mesh(self, op, spec: A.FoldSpec, part: C.Partition,
                          mesh):
        """Mesh-parallel fold: per-device shard reduction + psum over ICI
        (SURVEY §2.10: parallel aggregation via collectives)."""
        from ..parallel import collectives as CC
        from ..parallel import mesh as M

        if not part.leaves and part.fallback:
            raise NotCompilable("all-fallback partition")
        with TR.span("agg:segment-fold", "exec") as _sp:
            _sp.set("rows", part.num_rows).set("groups", 1)
            batch = C.stage_partition(part, self.backend.bucket_mode)
            arrays = M.pad_batch_for_mesh(batch.arrays,
                                          len(mesh.devices.flat))
            schema = part.schema
            eval_exprs = _make_eval_exprs(spec, schema)
            shapes = tuple(sorted((k, v.shape, str(v.dtype))
                                  for k, v in arrays.items()))
            run = self.backend.jit_cache.get_or_build(
                ("meshfold", op.id, schema.name, shapes,
                 self.backend.fn_cache_salt()),
                lambda: CC.sharded_fold_fn(eval_exprs, spec.reducers, mesh,
                                           arrays))
            outs = run(arrays)
            ok_np = M.materialize_np(outs[-1])[: part.num_rows] \
                & _real_mask(part)
            partials = [o.item() for o in outs[:-1]]
        bad = np.nonzero(~ok_np & _real_mask(part))[0].tolist()
        bad += [i for i in part.fallback if i not in bad]
        out = tuple(partials) if not spec.scalar else partials[0]
        return out, sorted(set(bad))

    def _device_fold_bykey(self, op, spec, part, kidx, groups, excs) -> bool:
        mesh = getattr(self.backend, "mesh", None)
        if mesh is not None:
            try:
                return self._device_fold_bykey_mesh(op, spec, part, kidx,
                                                    groups, excs, mesh)
            except NotCompilable:
                return False
        n = part.num_rows
        try:
            # staging, the eager expression ops and the fetch of the ok
            # mask (which waits for them)
            with TR.span("agg:eval-exprs", "exec") as _sp:
                _sp.set("rows", n)
                vals, ok_mask, err = self._eval_exprs(op, spec, part)
                ok_host = np.asarray(ok_mask)
        except NotCompilable:
            return False
        import jax.numpy as jnp
        import jax.ops

        ok_np = ok_host[:n] & _real_mask(part)
        with TR.span("agg:factorize-keys", "exec") as _sp:
            _sp.set("rows", n)
            codes, uniq_rows = _factorize_keys(part, kidx, ok_np)
            if codes is None:
                return False
            nseg = len(uniq_rows)
            _sp.set("groups", nseg)
            b = ok_host.shape[0]
            codes_b = np.full(b, nseg, dtype=np.int32)  # padding -> dropped
            codes_b[:n][ok_np] = codes
        seg_partials = []
        # eager segment reductions, one launch and one fetch a reducer
        # (`jit_scatter-add` on the device: not wrapped in a jit here,
        # ROADMAP S5)
        with TR.span("agg:segment-fold", "exec") as _sp:
            _sp.set("rows", n).set("groups", nseg)
            for cv_data, reducer in zip(vals, spec.reducers):
                is_float = cv_data.dtype.kind == "f"
                ident = _identity(reducer, is_float)
                masked = jnp.where(ok_mask, cv_data, ident)
                if reducer == "sum":
                    r = jax.ops.segment_sum(masked, codes_b,
                                            num_segments=nseg + 1)
                elif reducer == "min":
                    r = jax.ops.segment_min(masked, codes_b,
                                            num_segments=nseg + 1)
                else:
                    r = jax.ops.segment_max(masked, codes_b,
                                            num_segments=nseg + 1)
                seg_partials.append(np.asarray(r)[:nseg])
        with TR.span("agg:host-merge", "exec") as _sp:
            _sp.set("rows", n).set("groups", nseg)
            # merge per-key partials into the global dict (key columns only
            # — see decode_key_tuples: full decode would force lazy leaves)
            key_vals = C.decode_key_tuples(part, uniq_rows, kidx)
            for si, row_i in enumerate(uniq_rows):
                k = key_vals[si]
                acc = groups.get(k, op.initial)
                accs = list(acc) if isinstance(acc, tuple) else [acc]
                merged = []
                for j, reducer in enumerate(spec.reducers):
                    v = seg_partials[j][si].item()
                    merged.append(_combine_scalar(reducer, accs[j], v)
                                  if reducer != "sum" else accs[j] + v)
                groups[k] = tuple(merged) if isinstance(acc, tuple) \
                    else merged[0]
            # bad rows -> interpreter
            bad = np.nonzero(~ok_np & _real_mask(part))[0].tolist()
            bad += [i for i in part.fallback if i not in bad]
            self._python_fold(op, part, sorted(set(bad)), groups, kidx,
                              excs)
        return True

    def _device_fold_bykey_mesh(self, op, spec, part, kidx, groups, excs,
                                mesh) -> bool:
        """Grouped mesh aggregate: per-device segment reductions over the
        row shard, partial tables combined with psum/pmin/pmax over ICI
        (no shuffle — reference analog: per-task hashtables merged by
        createFinalHashmap, here merged on the interconnect)."""
        from ..parallel import collectives as CC
        from ..parallel import mesh as M

        if not part.leaves and part.fallback:
            raise NotCompilable("all-fallback partition")
        n = part.num_rows
        real = _real_mask(part)
        with TR.span("agg:factorize-keys", "exec") as _sp:
            _sp.set("rows", n)
            codes, uniq_rows = _factorize_keys(part, kidx, real)
            if codes is None:
                return False
            nseg = len(uniq_rows)
            _sp.set("groups", nseg)
        with TR.span("agg:segment-fold", "exec") as _sp:
            _sp.set("rows", n).set("groups", nseg)
            batch = C.stage_partition(part, self.backend.bucket_mode)
            arrays = M.pad_batch_for_mesh(batch.arrays,
                                          len(mesh.devices.flat))
            b = arrays["#rowvalid"].shape[0]
            codes_b = np.full(b, nseg, dtype=np.int32)  # padding -> dropped
            codes_b[:n][real] = codes
            schema = part.schema
            eval_exprs = _make_eval_exprs(spec, schema)
            shapes = tuple(sorted((k, v.shape, str(v.dtype))
                                  for k, v in arrays.items()))
            run = self.backend.jit_cache.get_or_build(
                ("meshseg", op.id, schema.name, nseg, shapes,
                 self.backend.fn_cache_salt()),
                lambda: CC.sharded_segment_fold_fn(
                    eval_exprs, spec.reducers, nseg, mesh, arrays))
            outs = run(arrays, codes_b)
            ok_np = M.materialize_np(outs[-1])[:n] & real
            counts = M.materialize_np(outs[-2])[:nseg]
            seg_partials = [np.asarray(o)[:nseg] for o in outs[:-2]]
        with TR.span("agg:host-merge", "exec") as _sp:
            _sp.set("rows", n).set("groups", nseg)
            key_vals = C.decode_key_tuples(part, uniq_rows, kidx)
            for si, row_i in enumerate(uniq_rows):
                if counts[si] == 0:
                    continue  # every row of this key failed: no ghost
                              # group — the interpreter fold below decides
                k = key_vals[si]
                acc = groups.get(k, op.initial)
                accs = list(acc) if isinstance(acc, tuple) else [acc]
                merged = [_combine_scalar(reducer, accs[j],
                                          seg_partials[j][si].item())
                          for j, reducer in enumerate(spec.reducers)]
                groups[k] = tuple(merged) if isinstance(acc, tuple) \
                    else merged[0]
            bad = np.nonzero(~ok_np & real)[0].tolist()
            bad += [i for i in part.fallback if i not in bad]
            self._python_fold(op, part, sorted(set(bad)), groups, kidx,
                              excs)
        return True

    # ------------------------------------------------------------------
    def _eval_exprs(self, op, spec: A.FoldSpec, part: C.Partition):
        """Evaluate fold exprs over the staged partition; returns
        (list of [B] arrays, ok_mask [B], err [B])."""
        from ..compiler.stagefn import input_row_cv
        import jax.numpy as jnp

        if not part.leaves and part.fallback:
            raise NotCompilable("all-fallback partition")
        batch = C.stage_partition(part, self.backend.bucket_mode)
        arrays = {k: jnp.asarray(v) for k, v in batch.arrays.items()}
        ctx = EmitCtx(batch.b, arrays["#rowvalid"])
        em = Emitter(ctx, spec.globals)
        row = input_row_cv(arrays, part.schema)
        frame = Frame(em, {spec.row_param: row})
        datas = []
        for expr in spec.exprs:
            cv = frame.eval(expr)
            cv = frame._require_numeric(cv, "aggregate expr")
            datas.append(cv.data)
        ok = arrays["#rowvalid"] & (ctx.err == 0)
        return datas, ok, ctx.err


def _make_eval_exprs(spec: A.FoldSpec, schema):
    """Emitter-traced fold expressions as a closure usable inside shard_map
    (shared by scalar and grouped mesh folds)."""
    from ..compiler.stagefn import input_row_cv

    def eval_exprs(arrs):
        ctx = EmitCtx(arrs["#rowvalid"].shape[0], arrs["#rowvalid"])
        em = Emitter(ctx, spec.globals)
        row = input_row_cv(arrs, schema)
        frame = Frame(em, {spec.row_param: row})
        datas = []
        for expr in spec.exprs:
            cv = frame.eval(expr)
            cv = frame._require_numeric(cv, "aggregate expr")
            datas.append(cv.data)
        ok = arrs["#rowvalid"] & (ctx.err == 0)
        return datas, ok

    return eval_exprs


def _real_mask(part: C.Partition) -> np.ndarray:
    m = np.ones(part.num_rows, dtype=np.bool_)
    if part.normal_mask is not None:
        m &= part.normal_mask
    return m


def _row_signatures(part: C.Partition) -> Optional[np.ndarray]:
    """[N] array of hashable per-row signatures (bytes), or None if the
    partition has non-vectorizable leaves. Invalid (None) slots are zeroed so
    every None has ONE canonical signature regardless of placeholder bytes."""
    pieces = []
    n = part.num_rows
    for path in sorted(part.leaves):
        leaf = part.leaves[path]
        if isinstance(leaf, C.NumericLeaf):
            data = leaf.data
            if leaf.valid is not None:
                data = np.where(leaf.valid, data, 0)
            pieces.append(np.ascontiguousarray(
                data.reshape(n, -1)).view(np.uint8).reshape(n, -1))
            if leaf.valid is not None:
                pieces.append(leaf.valid.reshape(-1, 1).view(np.uint8))
        elif isinstance(leaf, C.StrLeaf):
            b, ln = leaf.bytes, leaf.lengths
            if leaf.valid is not None:
                b = np.where(leaf.valid[:, None], b, 0)
                ln = np.where(leaf.valid, ln, 0)
            # zero padding past len (stage outputs may carry stale bytes)
            w = b.shape[1]
            b = np.where(np.arange(w)[None, :] < ln[:, None], b, 0)
            pieces.append(b)
            pieces.append(ln.astype("<i4").view(np.uint8).reshape(n, -1))
            if leaf.valid is not None:
                pieces.append(leaf.valid.reshape(-1, 1).view(np.uint8))
        elif isinstance(leaf, C.NullLeaf):
            continue
        else:
            return None
    if not pieces:
        return None
    mat = np.ascontiguousarray(np.concatenate(pieces, axis=1))
    return np.asarray([mat[i].tobytes() for i in range(n)], dtype=object)


def _factorize_keys(part: C.Partition, kidx: list[int], ok_mask: np.ndarray):
    """(codes[n_ok], unique_first_row_indices) — vectorized key factorization
    over the key columns' leaf bytes."""
    # canonical signatures: None slots zeroed, stale str padding zeroed —
    # raw leaf bytes would give the same python key distinct group codes
    # (same defect class as the joinexec Option-key bug)
    mat = C.key_signature_matrix(part, kidx, reject_nan=False)
    if mat is None:
        return None, None
    sub = mat[ok_mask]
    if len(sub) == 0:
        return np.zeros(0, np.int32), np.zeros(0, np.int64)
    inverse, first_idx = C.unique_rows(sub)
    ok_rows = np.nonzero(ok_mask)[0]
    return inverse, ok_rows[first_idx]
