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
from dataclasses import dataclass
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
    """Content-addressed compile for the fold executables (the agg
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
            table = _KeyTable()
            scan_k = None
            if spec is None and ps is not None and not getattr(
                    self.backend, "interpret_only", False):
                scan_k = A.ScanFold.try_build(op, ps)
            for part in partitions:
                self.backend.mm.touch(part)
                device_ok = spec is not None and self._device_fold_bykey(
                    op, spec, part, kidx, groups, excs, table)
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

    def _device_fold_bykey(self, op, spec, part, kidx, groups, excs,
                           table: "_KeyTable") -> bool:
        """One partition of a recognized by-key fold, in ONE stored
        executable: the fold expressions, the group of every row and the
        reduction of every expression for every group. While the keys met
        fit `_TABLE_MAX_SLOTS` the groups are the slots of `table`, matched
        on the device, which also finds the keys the table lacks: no key
        column leaves the chip and the host factorizes nothing. Above the
        capacity the same fold takes host-made codes."""
        mesh = getattr(self.backend, "mesh", None)
        if mesh is not None:
            try:
                return self._device_fold_bykey_mesh(op, spec, part, kidx,
                                                    groups, excs, mesh)
            except NotCompilable:
                return False
        if not part.leaves and part.fallback:
            return False     # all-fallback partition: interpreter
        n = part.num_rows
        real = _real_mask(part)
        # staging only (the handoff view where the stage left one, else the
        # upload): the expressions run inside the fold's executable
        with TR.span("agg:eval-exprs", "exec") as _sp:
            _sp.set("rows", n)
            batch = C.stage_partition(part, self.backend.bucket_mode)
            table.fit(_key_sig_plan(batch.arrays, part.schema, kidx))
        try:
            out = None
            while table.live:
                out = self._launch_bykey_fold(op, spec, part, kidx, batch,
                                              table=table)
                if not out.unmatched:
                    break
                out = None       # the table was full: a wider one, or none
                table.grow()
            if out is None:
                with TR.span("agg:factorize-keys", "exec") as _sp:
                    _sp.set("rows", n)
                    codes, uniq_rows = _factorize_keys(part, kidx, real)
                    if codes is None:
                        return False
                    _sp.set("groups", len(uniq_rows))
                    # key columns only — see decode_key_tuples: a full
                    # decode would force every lazy leaf to the host
                    key_vals = C.decode_key_tuples(part, uniq_rows, kidx)
                if len(uniq_rows):
                    out = self._launch_bykey_fold(
                        op, spec, part, kidx, batch,
                        host=(codes, real, key_vals))
        except NotCompilable:
            return False
        with TR.span("agg:host-merge", "exec") as _sp:
            _sp.set("rows", n)
            bad: list = []
            if out is not None:
                _sp.set("groups", int(np.count_nonzero(out.counts)))
                for si, k in out.keys:
                    if out.counts[si] == 0:
                        continue  # every row of this key failed: no ghost
                                  # group — the interpreter fold decides
                    acc = groups.get(k, op.initial)
                    accs = list(acc) if isinstance(acc, tuple) else [acc]
                    merged = [_combine_scalar(reducer, accs[j],
                                              out.partials[j][si].item())
                              for j, reducer in enumerate(spec.reducers)]
                    groups[k] = tuple(merged) if isinstance(acc, tuple) \
                        else merged[0]
                if out.n_bad:     # the only fetch that grows with the rows
                    ok_np = np.asarray(out.ok)[:n]
                    bad = np.nonzero(~ok_np & real)[0].tolist()
            # bad and boxed rows -> interpreter
            self._python_fold(op, part, sorted(set(bad) | set(part.fallback)),
                              groups, kidx, excs)
        return True

    def _launch_bykey_fold(self, op, spec, part, kidx, batch, table=None,
                           host=None) -> "_FoldOut":
        """Launch the by-key fold of one staged partition and fetch its
        small results: against `table`, which takes the keys it lacked
        (path "device-table"), or with what the host factorized, `host` =
        (codes of the real rows, the real mask, the codes' python keys)
        (path "host-codes")."""
        import jax

        from ..plan.physical import _op_identity

        if host is None:
            key, slots, nseg_b = table.rows, table.rows.shape[0], None
        else:
            codes, real, keys = host
            slots = nseg_b = C.bucket_size(len(keys), "pow2")
            key = np.full(batch.b, nseg_b, np.int32)   # padding -> dropped
            key[:part.num_rows][real] = codes
        with TR.span("agg:segment-fold", "exec") as _sp:
            _sp.set("slots", slots)
            fn = self.backend.jit_cache.get_or_build(
                ("aggfold", _op_identity(op), part.schema.name, tuple(kidx),
                 nseg_b),
                lambda: _aot(_make_bykey_fold(spec, part.schema, kidx,
                                              nseg_b),
                             "aggfold", op, part.schema))
            small, ok = fn(batch.arrays, key)
            small = jax.device_get(small)
            partials, counts, unmatched, n_bad = small[:4]
            if host is None:
                table.learn(part.schema, kidx, *small[4:])
            # `rows` are the rows this launch folded: a table too narrow
            # for the partition's keys folded none (it is launched again)
            _sp.set("rows", 0 if unmatched else part.num_rows) \
               .set("groups", int(np.count_nonzero(counts))) \
               .set("path", "host-codes" if host is not None
                    else "table-miss" if unmatched else "device-table")
        return _FoldOut(partials, counts, int(unmatched), int(n_bad), ok,
                        list(enumerate(keys)) if host is not None
                        else [(si, table.keys[si]) for si in table.order])

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


# Widest key table of the by-key fold: up to this many keys the fold matches
# rows against the table and reduces with masks (work K_b x B a reducer, no
# scatter, no key on the host); above it the fold takes host-made codes and
# reduces with segment_sum/min/max (work B a reducer, but a scatter the TPU
# serializes, and a host sort of every row's signature). On the TPU the
# masks win far beyond this; the number is held down by XLA:CPU, which runs
# the same code and scatters fast. The arithmetic is in CHANGES.md (PR 27).
_TABLE_MAX_SLOTS = 64


@dataclass
class _FoldOut:
    """What one launch of the by-key fold hands back (host values but
    `ok`, the [B] mask, which stays on the device until a row failed)."""
    partials: tuple          # per reducer: [slots] in the expression's dtype
    counts: np.ndarray       # [slots] ok rows
    unmatched: int           # ok rows whose key no slot holds
    n_bad: int               # normal rows the expressions failed on
    ok: Any
    keys: list               # (slot, python key) in signature order


class _KeyTable:
    """The canonical signatures (C.key_signature_matrix's form, at the
    staged batch's str widths) of the keys one by-key aggregate has met,
    as the `[K_b, 1 + W]` uint8 argument of the fold: byte 0 says a slot is
    taken, so a free slot matches no row. The fold itself fills free slots
    with the keys it meets (`learn` mirrors that on the host). The table
    grows by pow2 buckets (each a shape of the fold) and goes dead (`live`
    False: host-made codes from then on) past `_TABLE_MAX_SLOTS`, or where
    a key column's signature cannot be built on the device."""

    def __init__(self):
        self.live = True
        self.plan: Optional[tuple] = None
        self.rows: Optional[np.ndarray] = None
        self.keys: list = []             # slot -> python key tuple
        self.order: list = []            # slots by signature, ascending

    def fit(self, plan: Optional[tuple]) -> None:
        """Adopt the partition's signature layout; another layout than the
        table was built for (other str widths) starts it over."""
        if plan is None:
            self.live = False
        elif plan != self.plan:
            self.plan = plan
            self.rows = np.zeros((8, 1 + _sig_width(plan)), np.uint8)
            self.keys, self.order = [], []

    def learn(self, schema, kidx, rows, first, key_arrays) -> None:
        """Take over the table as a launch left it: `first[slot]` is the
        row that brought a new slot its key (else -1), `key_arrays` that
        row's key columns."""
        new = np.nonzero(first >= 0)[0]
        if not len(new):
            return
        arrs = {k: v[new] for k, v in key_arrays.items()}
        leaves = {path: C.leaf_from_result_arrays(arrs, path, lt, len(new))
                  for ci in kidx
                  for path, lt in C.flatten_type(schema.types[ci], str(ci))}
        mini = C.Partition(schema=schema, num_rows=len(new), leaves=leaves)
        self.keys += C.decode_key_tuples(mini, range(len(new)), kidx)
        self.rows = rows
        self.order = sorted(range(len(self.keys)),
                            key=lambda slot: rows[slot].tobytes())

    def grow(self) -> None:
        k_b = self.rows.shape[0]
        if 2 * k_b > _TABLE_MAX_SLOTS:
            self.live = False
        else:
            self.rows = C.pad_to(self.rows, 2 * k_b)


def _key_sig_plan(arrays: dict, schema, kidx) -> Optional[tuple]:
    """The pieces of a key signature as the staged arrays hold them, in
    C.key_signature_matrix's order: (kind, leaf path, bytes, has valid)
    per key column, or None where the device cannot build one (a tuple or
    host-only column; a float on a device whose float64 is no IEEE binary64
    and so has no bytes to compare). Reads keys, dtypes and shapes only."""
    from ..runtime.jaxcfg import f64_is_f32_pair

    plan = []
    for ci in kidx:
        leaves = C.flatten_type(schema.types[ci], str(ci))
        if len(leaves) != 1:
            return None
        (path, lt), = leaves
        valid = (path + "#valid") in arrays
        if path in arrays:
            a = arrays[path]
            if np.ndim(a) != 1 or a.dtype.kind not in "biuf" \
                    or (a.dtype.kind == "f" and f64_is_f32_pair()):
                return None
            plan.append(("num", path, a.dtype.itemsize, valid))
        elif (path + "#bytes") in arrays:
            plan.append(("str", path, arrays[path + "#bytes"].shape[1],
                         valid))
        elif not C.staged_keys_for_type(path, lt):
            plan.append(("null", path, 1, False))    # layout-free
        else:
            return None
    return tuple(plan) or None


def _sig_width(plan: tuple) -> int:
    """Bytes of one signature: each piece's own, its valid byte, and a
    str's four length bytes."""
    return sum(w + int(has_valid) + 4 * (kind == "str")
               for kind, _path, w, has_valid in plan)


def _device_key_signature(arrs: dict, plan: tuple):
    """[1 + W, B] uint8 on the device, rows along the minor (lane)
    dimension: a leading 1 (see _KeyTable), then byte for byte what
    C.key_signature_matrix builds on the host (at the staged str widths),
    transposed: equal signatures are equal python keys, and they sort as
    the host's do, which is the order groups are emitted in."""
    from ..runtime.jaxcfg import jnp, lax

    b = arrs["#rowvalid"].shape[0]
    pieces = [jnp.ones((1, b), jnp.uint8)]
    for kind, path, w, has_valid in plan:
        valid = arrs[path + "#valid"] if has_valid else None
        if kind == "null":
            pieces.append(jnp.zeros((1, b), jnp.uint8))
            continue
        if kind == "num":
            x = arrs[path]
            if valid is not None:
                x = jnp.where(valid, x, jnp.zeros((), x.dtype))
            if x.dtype.kind == "f":
                # on the bits: a float compare would take a denormal for
                # zero where the device flushes them, and numpy does not
                x = lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * w}"))
                x = jnp.where(x == jnp.asarray(1 << (8 * w - 1), x.dtype),
                              jnp.zeros((), x.dtype), x)       # -0.0
            pieces.append(_le_bytes(x, w))
        else:
            by, ln = arrs[path + "#bytes"].T, arrs[path + "#len"]
            if valid is not None:
                ln = jnp.where(valid, ln, 0)
            live = jnp.arange(by.shape[0])[:, None] < ln[None, :]
            pieces.append(jnp.where(live, by, jnp.uint8(0)))
            pieces.append(_le_bytes(ln.astype(jnp.int32), 4))
        if valid is not None:
            pieces.append(valid.astype(jnp.uint8)[None, :])
    return jnp.concatenate(pieces, axis=0)


def _le_bytes(x, w: int):
    """[B] bool or integers -> their [w, B] little-endian bytes, by shifts
    (a device's emulated int64 has no byte view)."""
    from ..runtime.jaxcfg import jnp

    if w == 1:
        return x.astype(jnp.uint8)[None, :]
    shifts = (8 * jnp.arange(w)).astype(x.dtype)
    return ((x[None, :] >> shifts[:, None]) & 0xFF).astype(jnp.uint8)


def _table_take_new_keys(table, sig, ok):
    """Fill the free slots of `table` [K_b, 1 + W] with the signatures of
    the ok rows no slot holds, a key an iteration, each from the first row
    that has it (so a partition without a new key iterates not once).
    Returns (table, first [K_b]: that row for a slot taken here, else -1,
    the ok rows still without a slot: the table is full)."""
    from ..runtime.jaxcfg import jnp, lax

    k_b = table.shape[0]

    def holds(tab):
        return jnp.any(jnp.all(tab[:, :, None] == sig[None, :, :], axis=1),
                       axis=0)

    def more(c):
        _tab, _first, lacking, used = c
        return (used < k_b) & jnp.any(lacking)

    def take(c):
        tab, first, lacking, used = c
        row = jnp.argmax(lacking).astype(jnp.int32)
        new = lax.dynamic_index_in_dim(sig, row, axis=1, keepdims=False)
        tab = lax.dynamic_update_index_in_dim(tab, new, used, axis=0)
        first = lax.dynamic_update_index_in_dim(first, row, used, axis=0)
        lacking = lacking & ~jnp.all(sig == new[:, None], axis=0)
        return tab, first, lacking, used + 1

    used = jnp.sum(table[:, 0], dtype=jnp.int32)
    tab, first, lacking, _ = lax.while_loop(
        more, take,
        (table, jnp.full((k_b,), -1, jnp.int32), ok & ~holds(table), used))
    return tab, first, lacking


def _make_bykey_fold(spec: A.FoldSpec, schema, kidx, nseg_b=None):
    """The by-key fold of one staged partition as one traceable function:
    fn(arrays, key) -> (small results, ok [B]), the small results being
    (per reducer the [slots] partials in the expression's dtype, [slots]
    counts of ok rows, ok rows no slot took, bad rows, ...).

      nseg_b None: `key` is the key table (an ARGUMENT: no key is a
        constant of the executable). The group of a row is the slot whose
        signature its key columns give, free slots first taking the keys
        the table lacks; every reduction is masked. Further small results:
        the table as it is now, `first` and the key columns of the `first`
        rows (see _KeyTable.learn).
      nseg_b int: `key` is the host's code a row (>= nseg_b: no group);
        every reduction is a segment reduction.
    """
    import jax

    from ..parallel.collectives import _ident_arr
    from ..runtime.jaxcfg import jnp

    eval_exprs = _make_eval_exprs(spec, schema)
    seg_reduce = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
                  "max": jax.ops.segment_max}

    def fn(arrays, key):
        vals, ok = eval_exprs(arrays)
        b = ok.shape[0]
        vals = [jnp.broadcast_to(jnp.asarray(v), (b,)) for v in vals]
        n_bad = jnp.sum(arrays["#rowvalid"] & ~ok, dtype=jnp.int32)
        if nseg_b is not None:
            partials = [
                seg_reduce[red](jnp.where(ok, v, _ident_arr(red, v.dtype)),
                                key, num_segments=nseg_b + 1)[:nseg_b]
                for v, red in zip(vals, spec.reducers)]
            counts = jax.ops.segment_sum(ok.astype(jnp.int32), key,
                                         num_segments=nseg_b + 1)[:nseg_b]
            return (tuple(partials), counts, jnp.zeros((), jnp.int32),
                    n_bad), ok
        plan = _key_sig_plan(arrays, schema, kidx)
        sig = _device_key_signature(arrays, plan)              # [1+W, B]
        table, first, lacking = _table_take_new_keys(key, sig, ok)
        match = jnp.all(table[:, :, None] == sig[None, :, :], axis=1)
        sel = match & ok[None, :]                              # [K_b, B]
        partials = []
        for v, red in zip(vals, spec.reducers):
            m = jnp.where(sel, v[None, :], _ident_arr(red, v.dtype))
            partials.append(getattr(m, red)(axis=1))
        counts = jnp.sum(sel, axis=1, dtype=jnp.int32)
        key_arrays = {
            k: jnp.take(arrays[k], jnp.maximum(first, 0), axis=0)
            for _kind, path, _w, _v in plan
            for k in (path, path + "#bytes", path + "#len", path + "#valid")
            if k in arrays}
        return (tuple(partials), counts,
                jnp.sum(lacking, dtype=jnp.int32), n_bad,
                table, first, key_arrays), ok

    return fn


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
