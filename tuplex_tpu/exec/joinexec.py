"""Join stage execution: broadcast build side + vectorized probe.

Reference model (reference: PhysicalPlan.cc:145-178 + LocalBackend.cc:213
executeHashJoinStage + HybridHashTable.h:46-60): the build side is fully
materialized into a hash table, broadcast to every task; the probe side
streams. Keys that can't live in the native table go to a python-dict backup
(hybrid). Here:

  * build: factorize build-side keys into sorted signatures (np.unique — C
    speed) + group offsets (CSR layout)
  * probe: per-partition vectorized signature match via np.searchsorted,
    match expansion via np.repeat, row materialization via leaf gathers
  * boxed fallback rows on either side probe/build through a python dict —
    the HybridHashTable semantics
"""

from __future__ import annotations

import time
from typing import Any, Optional

import numpy as np

from ..core import typesys as T
from ..core.row import Row
from ..runtime import columns as C
from ..runtime import tracing as TR
from .local import ExceptionRecord, StageResult


def _key_signatures(part: C.Partition, ci: int) -> Optional[np.ndarray]:
    """[N, W] canonical byte-signature matrix for the key column, None if the
    column isn't signature-comparable (see C.key_signature_matrix for the
    canonicalization contract — byte equality must imply python equality)."""
    return C.key_signature_matrix(part, [ci], reject_nan=True)


class JoinExecutor:
    def __init__(self, backend):
        self.backend = backend

    def execute(self, stage, left_partitions: list[C.Partition], context,
                intermediate=False):
        with TR.span("join:execute", "exec") as _sp:
            res = self._execute_impl(stage, left_partitions, context,
                                     intermediate=intermediate)
            if _sp is not TR.NOOP:
                _sp.set("rows_out", res.metrics.get("rows_out", 0))
                for k in ("columns_in", "columns_out", "filled"):
                    if res.metrics.get(k) is not None:
                        _sp.set(k, res.metrics[k])
        return res

    def _execute_impl(self, stage, left_partitions: list[C.Partition],
                      context, intermediate=False):
        from ..plan.physical import plan_stages

        op = stage.op
        t0 = time.perf_counter()
        # --- build side: execute the right sub-plan (stage N-1) ------------
        from ..api.dataset import _source_partitions

        with TR.span("join:build-side", "exec"):
            right_stages = plan_stages(op.right, context.options_store)
            rparts: Optional[list] = None
            excs: list[ExceptionRecord] = []
            for rs in right_stages:
                if rparts is None and \
                        getattr(rs, "source", None) is not None:
                    rparts = _source_partitions(context, rs)
                res = self.backend.execute_any(rs, rparts, context)
                rparts = res.partitions
                excs.extend(res.exceptions)

        # one path for ALL partitions so every output shares one schema;
        # device probe when a mesh/accelerator is present (or forced)
        vec = None
        if self._device_join_enabled():
            vec = _DeviceProbe.try_build(op, rparts or [], self.backend)
            if vec is not None:
                # device-resident OUTPUT: when a later stage consumes this
                # join, the match-expansion gathers stay on device and the
                # host leaves go lazy (jaxcfg gate per consumer kind)
                from ..runtime.jaxcfg import (device_handoff_budget_bytes,
                                              device_handoff_enabled)

                vec.dev_out = bool(intermediate) and device_handoff_enabled(
                    intermediate if isinstance(intermediate, str)
                    else "stage")
                if vec.dev_out:
                    vec._handoff_left = device_handoff_budget_bytes()
        if vec is None:
            vec = _VectorBuild.try_build(op, rparts or [], self.backend)
        if vec is not None and not all(
                vec.can_probe(part) for part in left_partitions):
            vec = None
        build = None
        out_parts = []
        self._filled = 0        # probe rows a left join filled with None
        for part in left_partitions:
            self.backend.mm.touch(part)
            with TR.span("join:probe", "exec") as _psp:
                _psp.set("rows", part.num_rows) \
                    .set("path", "device" if vec is not None else "host")
                if vec is not None:
                    outp = vec.probe(part, excs)
                    assert outp is not None
                else:
                    if build is None:
                        with TR.span("join:build-table", "exec"):
                            build = self._build_table(op, rparts or [])
                    outp = self._probe_partition(op, part, rparts or [],
                                                 build, excs)
            self.backend.mm.register(outp)
            out_parts.append(outp)
        from . import compilequeue as _cq

        cs, cn = _cq.consume_tag("join")
        m = {"wall_s": time.perf_counter() - t0,
             "rows_out": sum(p.num_rows for p in out_parts),
             "exception_rows": len(excs),
             "compile_s": cs, "stage_compiles": cn,
             "columns_out": len(stage.output_schema.columns or ())}
        if left_partitions:
            m["columns_in"] = len(left_partitions[0].schema.columns or ())
        if op.how == "left":
            m["filled"] = self._filled + (vec.filled if vec is not None
                                          else 0)
        return StageResult(out_parts, excs, m)

    # ------------------------------------------------------------------
    def _device_join_enabled(self) -> bool:
        """Device probe policy: 'auto' uses the device when the backend has
        a mesh or the platform is a real accelerator; CPU-local defaults to
        the host numpy probe (np.searchsorted is already C-speed there)."""
        mode = self.backend.options.get_str("tuplex.tpu.deviceJoin", "auto")
        if mode in ("true", "1", "yes"):
            return True
        if mode in ("false", "0", "no"):
            return False
        if getattr(self.backend, "mesh", None) is not None:
            return True
        try:
            from ..runtime.jaxcfg import jax

            return jax.devices()[0].platform != "cpu"
        except Exception:
            return False

    # ------------------------------------------------------------------
    def _build_table(self, op, rparts: list[C.Partition]) -> dict:
        """Hash table over the build side — rebuilt per execution (stale
        caches across actions would probe against old data)."""
        for rp in rparts:
            self.backend.mm.touch(rp)
        return _build_pydict(op, rparts)

    def _probe_partition(self, op, lpart: C.Partition,
                         rparts: list[C.Partition], build: dict,
                         excs: list) -> C.Partition:
        """Probe one left partition against the build table.

        Round-1 implementation materializes matches row-wise through decode
        (correct, host-bound); the vectorized leaf-gather fast path comes
        with the device join."""
        ls = lpart.schema
        lk = ls.columns.index(op.left_column)
        rs_cols_n = len(rparts[0].schema.columns) if rparts else \
            len(op.right.schema().columns)
        rkk = (rparts[0].schema.columns.index(op.right_column) if rparts
               else op.right.schema().columns.index(op.right_column))
        values = []
        single = len(ls.columns) == 1
        empty_right = (None,) * (rs_cols_n - 1)
        for vals in C.partition_to_pylist(lpart):
            row_vals = (vals,) if single else vals
            try:
                key = row_vals[lk]
                lvals = [v for i, v in enumerate(row_vals) if i != lk]
                matches = build.get(key, []) if _hashable(key) else []
            except Exception as e:
                excs.append(ExceptionRecord(op.id, type(e).__name__, vals))
                continue
            if matches:
                for m in matches:
                    rvals = [v for i, v in enumerate(m) if i != rkk]
                    values.append(tuple(lvals + [key] + rvals))
            elif op.how == "left":
                values.append(tuple(lvals) + (key,) + empty_right)
                self._filled += 1
        schema = op.schema()
        if not values:
            return C.Partition(schema=schema, num_rows=0, leaves={},
                               start_index=lpart.start_index)
        return C.build_partition(values, schema,
                                 start_index=lpart.start_index)


def _build_pydict(op, rparts: list[C.Partition]) -> dict:
    """python-dict build table over ALL rows (normal + boxed) — the backup
    side of the hybrid table and the row-wise path's table."""
    build: dict = {}
    for rp in rparts:
        rk = rp.schema.columns.index(op.right_column)
        single = len(rp.schema.columns) == 1
        for vals in C.partition_to_pylist(rp):
            row_vals = (vals,) if single else vals
            try:
                if not isinstance(row_vals, tuple) or rk >= len(row_vals):
                    continue
                build.setdefault(row_vals[rk], []).append(row_vals)
            except TypeError:
                pass  # unhashable build key: unreachable by probe
    return build


def _hashable(v) -> bool:
    try:
        hash(v)
        return True
    except TypeError:
        return False


def _concat_leaves(parts: list[C.Partition]) -> Optional[C.Partition]:
    """Concatenate partitions (same schema) into one; None if any leaf kind
    can't concatenate."""
    if not parts:
        return None
    C.harmonize_partitions(parts)
    schema = parts[0].schema
    paths = set(parts[0].leaves)
    if any(set(p.leaves) != paths for p in parts):
        return None
    leaves: dict[str, C.Leaf] = {}
    n = sum(p.num_rows for p in parts)
    for path in paths:
        ls = [p.leaves[path] for p in parts]
        if all(isinstance(l, C.NumericLeaf) for l in ls):
            data = np.concatenate([l.data for l in ls])
            valid = None
            if any(l.valid is not None for l in ls):
                valid = np.concatenate(
                    [l.valid if l.valid is not None
                     else np.ones(len(l), np.bool_) for l in ls])
            leaves[path] = C.NumericLeaf(data, valid)
        elif all(isinstance(l, C.StrLeaf) for l in ls):
            leaves[path] = C.StrLeaf(
                np.concatenate([l.bytes for l in ls]),
                np.concatenate([l.lengths for l in ls]),
                np.concatenate([l.valid if l.valid is not None
                                else np.ones(len(l), np.bool_)
                                for l in ls])
                if any(l.valid is not None for l in ls) else None)
        elif all(isinstance(l, C.NullLeaf) for l in ls):
            leaves[path] = C.NullLeaf(n)
        else:
            return None
    return C.Partition(schema=schema, num_rows=n, leaves=leaves)


def _gather_leaves(part: C.Partition, idx: np.ndarray, valid_rows=None
                   ) -> Optional[dict]:
    """Leaf dict gathered at idx; rows where valid_rows is False become
    invalid slots (left-join None fill)."""
    out: dict[str, C.Leaf] = {}
    m = len(idx)
    for path, leaf in part.leaves.items():
        if isinstance(leaf, C.NumericLeaf):
            data = leaf.data[idx] if m else leaf.data[:0]
            valid = leaf.valid[idx] if leaf.valid is not None and m else (
                leaf.valid[:0] if leaf.valid is not None else None)
            if valid_rows is not None:
                v = valid if valid is not None else np.ones(m, np.bool_)
                valid = v & valid_rows
                data = np.where(valid_rows, data, 0)
            out[path] = C.NumericLeaf(data, valid)
        elif isinstance(leaf, C.StrLeaf):
            b = leaf.bytes[idx] if m else leaf.bytes[:0]
            ln = leaf.lengths[idx] if m else leaf.lengths[:0]
            valid = leaf.valid[idx] if leaf.valid is not None and m else (
                leaf.valid[:0] if leaf.valid is not None else None)
            if valid_rows is not None:
                v = valid if valid is not None else np.ones(m, np.bool_)
                valid = v & valid_rows
            out[path] = C.StrLeaf(b, ln, valid)
        elif isinstance(leaf, C.NullLeaf):
            out[path] = C.NullLeaf(m)
        else:
            return None
    return out


class _VectorBuild:
    """Vectorized broadcast-join build: unique build keys + CSR row groups,
    with HYBRID handling of boxed rows (reference: HybridHashTable.h:46-60 —
    compiled keys in the native table, incompatible rows in a python backup).

    Normal-case rows on both sides match via canonical byte signatures
    (np.unique + searchsorted — no per-row python on the hot path). Boxed
    probe rows python-probe the full dict; boxed BUILD rows with conforming
    keys get signatures so normal probe rows still find them (their output
    rows box through the partition fallback slots). Cross-type boxed build
    keys reject the vectorized path entirely — python `==` semantics there
    need the row-wise dict."""

    @classmethod
    def try_build(cls, op, rparts: list[C.Partition], backend):
        if not rparts:
            return None
        for p in rparts:
            backend.mm.touch(p)
        big = _concat_leaves(rparts)
        if big is None or big.num_rows == 0:
            return None  # empty build: row-wise path handles it
        rk = big.schema.columns.index(op.right_column)
        rt = big.schema.types[rk]
        n_cols = len(big.schema.columns)
        # boxed build rows -> backup side
        boxed_rows: list[tuple] = []
        normal_mask_all = np.ones(big.num_rows, np.bool_)
        off = 0
        for rp in rparts:
            single = len(rp.schema.columns) == 1
            for i, v in rp.fallback.items():
                row_vals = (v,) if single and not (
                    isinstance(v, tuple) and len(v) == n_cols) else v
                if not isinstance(row_vals, tuple) or \
                        len(row_vals) != n_cols:
                    return None      # arity-weird boxed rows: row-wise path
                normal_mask_all[off + i] = False
                if row_vals[rk] is None and not rt.is_optional():
                    # a build row without a key (an airport the database
                    # gives no code): only a probe key that is None equals
                    # it, and no normal probe row holds one (can_probe
                    # takes a key column of the build's own type, which is
                    # no Option), so the row lives in the backup dict
                    # alone, for boxed probe rows
                    continue
                if not T.python_value_conforms(row_vals[rk], rt):
                    return None      # cross-type key: python == semantics
                boxed_rows.append(tuple(row_vals))
            off += rp.num_rows
        normal_idx = np.nonzero(normal_mask_all)[0]
        if len(normal_idx) == 0:
            return None   # all-boxed build: nothing to sign; row-wise path
        sig = _key_signatures(big, rk)
        if sig is None:
            return None
        sub = np.ascontiguousarray(sig[normal_idx])
        view = sub.view([("v", np.void, sig.shape[1])]).ravel()
        uniq, inverse = np.unique(view, return_inverse=True)
        order = np.argsort(inverse, kind="stable")
        counts = np.bincount(inverse, minlength=len(uniq))
        offsets = np.concatenate([[0], np.cumsum(counts)])
        self = cls()
        self.op = op
        self.big = big
        self.rk = rk
        self.rparts = rparts
        self.uniq_view = uniq
        self.order = normal_idx[order]        # global big-row indices
        self.counts = counts
        self.offsets = offsets
        self.key_width = sig.shape[1]
        self.boxed_rows = boxed_rows
        self.boxed_sigs = None
        self.filled = 0         # probe rows a left join filled with None
        self._sigs: dict = {}   # id(partition) -> signatures, can_probe's
        self._pydict: Optional[dict] = None
        if boxed_rows and not self._encode_boxed_sigs(rt):
            return None              # can't sign boxed keys: stay exact
        return self

    def _encode_boxed_sigs(self, rt) -> bool:
        """Signatures for boxed build keys in the SAME byte layout as the
        normal-case key column (width-padded); keys too long for the layout
        are unreachable by normal probe rows and sign as all-0xFF sentinels
        (never equal to a canonical signature's zero padding)."""
        kschema = T.row_of(["k"], [rt])
        kpart = C.build_partition([r[self.rk] for r in self.boxed_rows],
                                  kschema)
        if kpart.fallback:
            return False
        too_long = np.zeros(kpart.num_rows, np.bool_)
        for path, leaf in kpart.leaves.items():
            if isinstance(leaf, C.StrLeaf):
                big_path = str(self.rk) + path[1:]
                big_leaf = self.big.leaves.get(big_path)
                if not isinstance(big_leaf, C.StrLeaf):
                    return False
                w = big_leaf.width
                too_long |= leaf.lengths > w
                if leaf.width < w:
                    leaf.bytes = C.pad_to(leaf.bytes, w, axis=1)
                elif leaf.width > w:
                    leaf.bytes = np.ascontiguousarray(leaf.bytes[:, :w])
        sigs = C.key_signature_matrix(kpart, [0], reject_nan=True)
        if sigs is None or sigs.shape[1] != self.key_width:
            return False
        sigs = np.where(too_long[:, None], np.uint8(0xFF), sigs)
        self.boxed_sigs = sigs
        return True

    def _full_pydict(self) -> dict:
        if self._pydict is None:
            self._pydict = _build_pydict(self.op, self.rparts)
        return self._pydict

    def can_probe(self, lpart: C.Partition) -> bool:
        """Cheap qualification; ALL partitions must pass or the whole join
        uses the row-wise path (mixed paths would mix output schemas). The
        signatures are kept for `probe`, which asks next."""
        op = self.op
        if op.left_column not in lpart.schema.columns:
            return False
        lk = lpart.schema.columns.index(op.left_column)
        sig = self._probe_signatures(lpart, lk)
        if sig is not None:
            self._sigs[id(lpart)] = sig
        return sig is not None

    def _probe_signatures(self, lpart: C.Partition, lk: int
                          ) -> Optional[np.ndarray]:
        """The probe key's signatures in the build side's byte layout, or
        None where byte equality would not be python equality (i64 against
        f64 keys). Keys of one type and width take `key_signature_matrix`
        as they are. A string key against an Option of a string, or at
        another width (a stage's output keeps its own; harmonize covers
        one dataset's partitions), goes there as a one-column view at the
        build key's type and width, so the layout has one definition; a
        key that can equal no build key (longer than its width; None
        against a plain string) signs as all-0xFF, as boxed keys do."""
        lt = lpart.schema.types[lk]
        rt = self.big.schema.types[self.rk]
        if lt.name == rt.name:
            sig = _key_signatures(lpart, lk)
            if sig is None or sig.shape[1] == self.key_width:
                return sig
        leaf = lpart.leaves.get(str(lk))
        bleaf = self.big.leaves.get(str(self.rk))
        if lt.without_option() is not T.STR or \
                rt.without_option() is not T.STR or \
                not isinstance(leaf, C.StrLeaf) or \
                not isinstance(bleaf, C.StrLeaf):
            return None
        n, w = lpart.num_rows, bleaf.width
        never = leaf.lengths > w
        valid = leaf.valid
        if bleaf.valid is None and valid is not None:
            never, valid = never | ~valid, None
        elif bleaf.valid is not None and valid is None:
            valid = np.ones(n, np.bool_)
        view = C.Partition(
            schema=T.row_of(["k"], [rt]), num_rows=n,
            leaves={"0": C.StrLeaf(
                np.ascontiguousarray(C.pad_to(leaf.bytes, w, axis=1)[:, :w]),
                leaf.lengths, valid)})
        sig = _key_signatures(view, 0)
        if sig is None or sig.shape[1] != self.key_width:
            return None
        return np.where(never[:, None], np.uint8(0xFF), sig)

    def probe(self, lpart: C.Partition, excs: list
              ) -> Optional[C.Partition]:
        sig = self._sigs.pop(id(lpart), None)
        if sig is None:
            lk = lpart.schema.columns.index(self.op.left_column)
            sig = self._probe_signatures(lpart, lk)
        if sig is None:
            return None
        return self._probe_sig(lpart, sig, excs)

    def _match_positions(self, sig: np.ndarray):
        """(pos_clipped [N], matched [N]) — lower-bound probe into the sorted
        unique build signatures. Host numpy; _DeviceProbe overrides with the
        on-device binary search."""
        view = np.ascontiguousarray(sig).view(
            [("v", np.void, sig.shape[1])]).ravel()
        pos = np.searchsorted(self.uniq_view, view)
        pos_c = np.clip(pos, 0, len(self.uniq_view) - 1)
        matched = (pos < len(self.uniq_view)) & \
            (self.uniq_view[pos_c] == view)
        return pos_c, matched

    def _gather(self, part: C.Partition, idx: np.ndarray, valid_rows=None
                ) -> Optional[dict]:
        """Leaf gather for the match expansion; _DeviceProbe overrides with
        jitted device gathers."""
        return _gather_leaves(part, idx, valid_rows)

    def _output_layout(self, ls: T.RowType):
        """(out_cols, out_types, entries) where entries[i] = (side,
        src_ci, make_opt) maps output column i to its source column —
        the single definition of the join's output column order, shared
        by the host and device assemblies."""
        op = self.op
        rs = self.big.schema
        lk = ls.columns.index(op.left_column)
        out_cols: list[str] = []
        out_types: list = []
        entries: list[tuple[str, int, bool]] = []
        for i, (c, t) in enumerate(zip(ls.columns, ls.types)):
            if i == lk:
                continue
            out_cols.append(op._decorate(c, 0))
            out_types.append(t)
            entries.append(("l", i, False))
        out_cols.append(op.left_column)
        out_types.append(ls.types[lk])
        entries.append(("l", lk, False))
        for i, (c, t) in enumerate(zip(rs.columns, rs.types)):
            if i == self.rk:
                continue
            out_cols.append(op._decorate(c, 1))
            mo = op.how == "left"
            out_types.append(T.option(t) if mo else t)
            entries.append(("r", i, mo))
        return out_cols, out_types, entries

    def _probe_sig(self, lpart: C.Partition, sig: np.ndarray, excs: list
                   ) -> Optional[C.Partition]:
        plan = self._probe_plan(lpart, sig, excs)
        return self._assemble(lpart, plan, device=False)

    def _assemble(self, lpart: C.Partition, plan: dict, device: bool
                  ) -> Optional[C.Partition]:
        """The join output from the gather program, inside `join:assemble`
        (its gathers inside `join:gather`)."""
        with TR.span("join:assemble", "exec") as _sp:
            _sp.set("rows_out", int(plan["m"])) \
               .set("path", "device" if device else "host")
            if device:
                return self._assemble_device(lpart, plan)
            return self._assemble_host(lpart, plan)

    def _probe_plan(self, lpart: C.Partition, sig: np.ndarray,
                    excs: list) -> dict:
        """Host-side match planning shared by the host and device
        assemblies: per-row match counts, boxed-row splices, output slot
        layout, and the flat (left_idx, build_rows, has_match) gather
        program for the vectorized portion."""
        op = self.op
        ls = lpart.schema
        lk = ls.columns.index(op.left_column)
        n = lpart.num_rows
        fb = lpart.fallback
        is_fb = np.zeros(n, np.bool_)
        if fb:
            is_fb[list(fb.keys())] = True
        pos_c, matched = self._match_positions(sig)
        matched = matched & ~is_fb   # boxed slots carry placeholder bytes
        cnt = np.where(matched, self.counts[pos_c], 0).astype(np.int64)

        # boxed-build matches for normal probe rows, and python probes for
        # boxed probe rows — each lands as a boxed OUTPUT row in its slot
        extra_rows: dict[int, list] = {}
        bcnt = np.zeros(n, np.int64)
        ncols_r = len(self.big.schema.columns)
        if self.boxed_sigs is not None and len(self.boxed_sigs):
            # loop over the (small) boxed side: a broadcast [N, B, W] compare
            # would transiently allocate N*B*W bytes on large probes
            cand = np.zeros((n, len(self.boxed_sigs)), np.bool_)
            for bi in range(len(self.boxed_sigs)):
                cand[:, bi] = (sig == self.boxed_sigs[bi][None, :]).all(-1)
            cand &= ~is_fb[:, None]
            rows_with_b = np.nonzero(cand.any(1))[0]
            for i, row in zip(rows_with_b.tolist(),
                              C.decode_rows(lpart, rows_with_b)):
                row_vals = tuple(row.values)
                key = row_vals[lk]
                lvals = [x for j, x in enumerate(row_vals) if j != lk]
                outs = []
                for bi in np.nonzero(cand[i])[0].tolist():
                    mrow = self.boxed_rows[bi]
                    rvals = [x for j, x in enumerate(mrow) if j != self.rk]
                    outs.append(tuple(lvals + [key] + rvals))
                extra_rows[i] = outs
            bcnt[rows_with_b] = cand[rows_with_b].sum(1)
        if fb:
            pydict = self._full_pydict()
            for i, v in fb.items():
                row_vals = v if isinstance(v, tuple) else (v,)
                try:
                    key = row_vals[lk]
                    lvals = [x for j, x in enumerate(row_vals) if j != lk]
                    matches = pydict.get(key, []) if _hashable(key) else []
                except Exception as e:
                    excs.append(ExceptionRecord(op.id, type(e).__name__, v))
                    continue
                outs = []
                for mrow in matches:
                    rvals = [x for j, x in enumerate(mrow) if j != self.rk]
                    outs.append(tuple(lvals + [key] + rvals))
                if not outs and op.how == "left":
                    outs.append(tuple(lvals) + (key,) +
                                (None,) * (ncols_r - 1))
                    self.filled += 1
                if outs:
                    extra_rows[i] = outs
                bcnt[i] = len(outs)

        total = cnt + bcnt
        filler = np.zeros(n, np.bool_)
        if op.how == "left":
            filler = (total == 0) & ~is_fb
            self.filled += int(filler.sum())
        out_per_row = np.where(filler, 1, total)
        m = int(out_per_row.sum())
        starts = np.concatenate([[0], np.cumsum(out_per_row)])[:-1]

        # ---- vectorized portion: signature matches (+ left-join fillers) --
        vec_take = np.where(filler, 1, cnt)
        m_vec = int(vec_take.sum())
        left_idx = np.repeat(np.arange(n), vec_take)
        row_starts = np.concatenate([[0], np.cumsum(vec_take)])[:-1]
        intra = np.arange(m_vec) - np.repeat(row_starts, vec_take)
        code = self.offsets[np.repeat(pos_c, vec_take)]
        has_match = np.repeat(matched, vec_take)
        build_rows = np.where(
            has_match, self.order[np.clip(code + intra, 0,
                                          max(len(self.order) - 1, 0))], 0)
        # output slot of each vectorized row: row start + intra-group rank
        vec_slots = np.repeat(starts, vec_take) + intra
        return {"lk": lk, "is_fb": is_fb, "cnt": cnt,
                "extra_rows": extra_rows, "starts": starts, "m": m,
                "m_vec": m_vec, "left_idx": left_idx,
                "build_rows": build_rows, "has_match": has_match,
                "vec_slots": vec_slots}

    def _assemble_host(self, lpart: C.Partition, plan: dict
                       ) -> Optional[C.Partition]:
        """Materialize the join output on host from the gather program."""
        op = self.op
        ls = lpart.schema
        left_idx = plan["left_idx"]
        build_rows = plan["build_rows"]
        has_match = plan["has_match"]
        m_vec = plan["m_vec"]
        m = plan["m"]
        extra_rows = plan["extra_rows"]
        # gather left (minus key), key, right (minus key)
        with TR.span("join:gather", "exec") as _sp:
            _sp.set("rows_out", m_vec).set(
                "columns", len(ls.columns) + len(self.big.schema.columns) - 1)
            lgather = self._gather(lpart, left_idx)
            rgather = self._gather(self.big, build_rows,
                                   valid_rows=has_match
                                   if op.how == "left" else None)
        if lgather is None or rgather is None:
            return None
        out_cols, out_types, entries = self._output_layout(ls)
        leaves: dict[str, C.Leaf] = {}
        for ci_out, (side, src_ci, _mo) in enumerate(entries):
            src_leaves = lgather if side == "l" else rgather
            for path, leaf in src_leaves.items():
                if path == str(src_ci) or path.startswith(f"{src_ci}.") or \
                        path.startswith(f"{src_ci}#"):
                    # make_opt leaves already carry validity: _gather_leaves
                    # was called with valid_rows=has_match for left joins
                    newp = str(ci_out) + path[len(str(src_ci)):]
                    leaves[newp] = leaf
        schema = T.row_of(out_cols, out_types)
        vec_part = C.Partition(schema=schema, num_rows=m_vec, leaves=leaves,
                               start_index=lpart.start_index)
        if not extra_rows:
            return vec_part
        # ---- splice boxed outputs into their slots ------------------------
        starts, cnt, is_fb = plan["starts"], plan["cnt"], plan["is_fb"]
        vec_slots = plan["vec_slots"]
        outp = C.gather_partition(vec_part, vec_slots,
                                  np.arange(m_vec, dtype=np.int64), m)
        outp.start_index = lpart.start_index
        mask = np.zeros(m, np.bool_)
        mask[vec_slots] = True
        fallback_out: dict[int, Any] = {}
        for i, outs in extra_rows.items():
            base = int(starts[i]) + (int(cnt[i]) if not is_fb[i] else 0)
            for j, t in enumerate(outs):
                fallback_out[base + j] = t
        outp.normal_mask = mask
        outp.fallback = fallback_out
        return outp


# ===========================================================================
# device-side probe + gather (SURVEY §2.10.4: device-sharded broadcast join)
# ===========================================================================

def _pack_sig_words(sig: np.ndarray) -> np.ndarray:
    """[N, W] uint8 canonical signatures -> [N, nw] uint64 words whose
    word-sequence lexicographic order equals the byte lexicographic order
    (big-endian packing), so the device can binary-search them."""
    n, w = sig.shape
    nw = max(1, -(-w // 8))
    if w < nw * 8:
        sig = np.concatenate(
            [sig, np.zeros((n, nw * 8 - w), np.uint8)], axis=1)
    return np.ascontiguousarray(sig).view(">u8").astype(np.uint64)


def _build_probe_fn(u: int, nw: int, mesh=None):
    """Jittable lower-bound binary search of [B, nw] probe words in the
    sorted [u, nw] build words. On a mesh the probe rows shard over the data
    axis while the build side replicates on every device — the broadcast
    hash join of the reference (PhysicalPlan.cc:145-178: no shuffle, build
    side fully materialized everywhere)."""
    from ..runtime.jaxcfg import jax, jnp

    steps = max(1, u).bit_length() + 1

    # direct rank probe: lower_bound[n] = |{j : build[j] <lex probe[n]}| as
    # one fused comparison/reduction pass — no per-step row gathers. The
    # binary search's build_words[mid] gathers run on the TPU scalar core
    # (the profiled zillow-stage gathers cost ~49ms each at this batch
    # size); the [B, u, nw] comparison streams through the VPU instead.
    # Falls back to the log-step search when the broadcast build side is
    # large enough that the B x u compare matrix would out-cost it.
    direct = u * max(1, nw) <= (1 << 15)
    # the loop-carried [chunk, u] less/prefix_eq intermediates are bounded
    # by chunking the probe batch: an unchunked 1M-row bucket against
    # u=32768 would carry multi-GB booleans per dispatch if XLA doesn't
    # fuse the chain into the reductions (ADVICE r5) — cap chunk*u*nw
    _DIRECT_CHUNK_ELEMS = 1 << 22

    def _lower_bound_direct_one(words, build_words):
        bw = build_words[None, :, :]          # [1, u, nw]
        pw = words[:, None, :]                # [chunk, 1, nw]
        lt = bw < pw
        eq = bw == pw
        b = words.shape[0]
        less = jnp.zeros((b, u), dtype=bool)
        prefix_eq = jnp.ones((b, u), dtype=bool)
        for k in range(nw):                   # nw is tiny (key bytes / 8)
            less = less | (prefix_eq & lt[..., k])
            prefix_eq = prefix_eq & eq[..., k]
        pos = less.sum(axis=1, dtype=jnp.int32)
        matched = prefix_eq.any(axis=1)       # some build row fully equal
        return (jnp.clip(pos, 0, max(u - 1, 0)).astype(jnp.int64),
                matched)

    def lower_bound_direct(words, build_words):
        b = words.shape[0]
        chunk = max(1, _DIRECT_CHUNK_ELEMS // max(1, u * max(1, nw)))
        if b <= chunk:
            return _lower_bound_direct_one(words, build_words)
        nchunks = -(-b // chunk)
        pad = nchunks * chunk - b
        wpad = jnp.pad(words, ((0, pad), (0, 0))) if pad else words
        pos, matched = jax.lax.map(
            lambda w: _lower_bound_direct_one(w, build_words),
            wpad.reshape(nchunks, chunk, wpad.shape[1]))
        return pos.reshape(-1)[:b], matched.reshape(-1)[:b]

    def lower_bound_search(words, build_words):
        b = words.shape[0]
        lo = jnp.zeros(b, jnp.int32)
        hi = jnp.full(b, u, jnp.int32)
        for _ in range(steps):
            done = lo >= hi
            mid = (lo + hi) // 2
            mw = build_words[jnp.clip(mid, 0, max(u - 1, 0))]   # [b, nw]
            diff = mw != words
            anyd = jnp.any(diff, axis=1)
            first = jnp.argmax(diff, axis=1)
            aw = jnp.take_along_axis(mw, first[:, None], 1)[:, 0]
            bw = jnp.take_along_axis(words, first[:, None], 1)[:, 0]
            less = anyd & (aw < bw)
            lo = jnp.where(~done & less, mid + 1, lo)
            hi = jnp.where(~done & ~less, mid, hi)
        pos = jnp.clip(lo, 0, max(u - 1, 0))
        cand = build_words[pos]
        matched = (lo < u) & jnp.all(cand == words, axis=1)
        return pos.astype(jnp.int64), matched

    lower_bound = lower_bound_direct if direct else lower_bound_search

    if mesh is None:
        # content-addressed compile (exec/compilequeue): flights' probe
        # stages are isomorphic up to the build table — which is an
        # ARGUMENT here, so equal (u, nw) probes share one executable
        # in-process and reuse the serialized artifact across processes
        from .compilequeue import aot_jit

        return aot_jit(TR.name_fn(lower_bound, "joinprobe",
                                  TR.key8(u, nw, direct)), tag="join")
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS
    from ..runtime.jaxcfg import shard_map_compat

    sharded = shard_map_compat(lower_bound, mesh,
                               (P(DATA_AXIS), P()),
                               (P(DATA_AXIS), P(DATA_AXIS)))

    def fn(words, build_words):
        return sharded(words, build_words)

    return jax.jit(TR.name_fn(fn, "joinprobe", TR.key8(
        u, nw, direct, tuple(mesh.devices.shape))))


def _leaf_flat_arrays(part: C.Partition, prefix: str) -> Optional[dict]:
    """Flatten a partition's leaves into a dict of arrays for the device
    gather; None if any leaf kind can't ride the device."""
    out: dict[str, np.ndarray] = {}
    for path, leaf in part.leaves.items():
        if isinstance(leaf, C.NumericLeaf):
            out[f"{prefix}{path}#d"] = leaf.data
            if leaf.valid is not None:
                out[f"{prefix}{path}#v"] = leaf.valid
        elif isinstance(leaf, C.StrLeaf):
            out[f"{prefix}{path}#b"] = leaf.bytes
            out[f"{prefix}{path}#l"] = leaf.lengths
            if leaf.valid is not None:
                out[f"{prefix}{path}#v"] = leaf.valid
        elif isinstance(leaf, C.NullLeaf):
            pass                      # rebuilt host-side from m
        else:
            return None
    return out


def _build_assemble_fn(pairs: tuple, left_join: bool):
    """Jittable join-output assembly: gathers every source leaf array at
    the match-expansion indices and emits OUTPUT-convention keys (path /
    path#bytes / path#len / path#valid) so the result doubles as the
    output partition's device view. pairs: (outkey, side, srckey|None,
    suffix) with suffix 'synth_v' synthesizing Option validity for left
    joins whose build side had none."""
    from ..runtime.jaxcfg import jax, jnp

    def fn(larr, rarr, lidx, ridx, hm):
        out = {}
        for outkey, side, srckey, suf in pairs:
            if suf == "synth_v":
                out[outkey] = hm
                continue
            src = larr if side == "l" else rarr
            idx = lidx if side == "l" else ridx
            g = src[srckey][idx]
            if side == "r" and left_join:
                if suf == "v":
                    g = g & hm
                elif suf == "d":
                    shape = (hm.shape[0],) + (1,) * (g.ndim - 1)
                    g = jnp.where(hm.reshape(shape), g, 0)
            out[outkey] = g
        return out

    from .compilequeue import aot_jit

    return aot_jit(TR.name_fn(fn, "joinassemble",
                              TR.key8(pairs, left_join)),
                   salt=f"assemble{int(left_join)}", tag="join")


def _build_gather_fn(lkeys: tuple, rkeys: tuple, left_join: bool):
    """Jittable match-expansion gather: output row i takes left row
    left_idx[i] and build row build_rows[i]; for left joins the unmatched
    rows' right side is invalidated on device."""
    from ..runtime.jaxcfg import jax, jnp

    def gather(left_arrays, build_arrays, left_idx, build_rows, has_match):
        out = {}
        for k in lkeys:
            out[k] = left_arrays[k][left_idx]
        for k in rkeys:
            g = build_arrays[k][build_rows]
            if left_join:
                if k.endswith("#v"):
                    g = g & has_match
                elif k.endswith("#d"):
                    shape = (has_match.shape[0],) + (1,) * (g.ndim - 1)
                    g = jnp.where(has_match.reshape(shape), g, 0)
            out[k] = g
        return out

    from .compilequeue import aot_jit

    return aot_jit(TR.name_fn(gather, "joingather",
                              TR.key8(lkeys, rkeys, left_join)),
                   salt=f"gather{int(left_join)}", tag="join")


class _DeviceProbe(_VectorBuild):
    """Broadcast join with the probe + gathers ON DEVICE (single chip or
    mesh). The build side stays host-factorized (np.unique — it is the small
    side by the reference's own cost model) and ships to the device once;
    probe partitions search it with a vectorized binary search and expand
    matches with device gathers. Reference: PipelineBuilder.h
    innerJoinDict/leftJoinDict fused probes; HashJoinStage.cc:473.

    With `dev_out` set (the join feeds a later stage and the handoff gate
    allows it), the match-expansion output stays ON DEVICE: the result
    partition carries a device view for the consumer and lazy host leaves
    that fetch only if some slow path needs them."""

    dev_out = False

    @classmethod
    def try_build(cls, op, rparts, backend):
        self = super().try_build(op, rparts, backend)
        if self is None:
            return None
        if _leaf_flat_arrays(self.big, "r.") is None:
            return None
        u = len(self.uniq_view)
        sig_bytes = self.uniq_view.view(np.uint8).reshape(u, -1)
        self._build_words = _pack_sig_words(sig_bytes)
        self._nw = self._build_words.shape[1]
        self._mesh = getattr(backend, "mesh", None)
        self.backend = backend
        self._rflat_dev = None
        return self

    # ------------------------------------------------------------------
    def _probe_sig(self, lpart: C.Partition, sig: np.ndarray, excs: list
                   ) -> Optional[C.Partition]:
        plan = self._probe_plan(lpart, sig, excs)
        if self.dev_out and self._mesh is None and not plan["extra_rows"]:
            outp = self._assemble(lpart, plan, device=True)
            if outp is not None:
                return outp
        return self._assemble(lpart, plan, device=False)

    def _assemble_device(self, lpart: C.Partition, plan: dict
                         ) -> Optional[C.Partition]:
        """Device-resident join output: one jitted gather writes the
        output-convention arrays; host leaves go lazy and the next stage
        consumes the attached view directly. Best-effort — None falls back
        to the host assembly (identical semantics)."""
        try:
            import jax

            from ..runtime import xferstats
            from ..runtime.jaxcfg import jnp

            op = self.op
            m = int(plan["m"])
            if m == 0 or plan["m_vec"] != m:
                return None
            out_cols, out_types, entries = self._output_layout(lpart.schema)
            rs = self.big.schema
            for side, src_ci, mo in entries:
                if not mo:
                    continue
                base = rs.types[src_ci]
                base = base.without_option() if base.is_optional() else base
                if isinstance(base, T.TupleType) or \
                        base in (T.NULL, T.EMPTYTUPLE):
                    return None   # nested Option synthesis: host path
            # PEEK the input view: every bail below must leave it intact
            # for the host assembly (a burnt view would force a full
            # lazy-leaf D2H — worse than no handoff at all)
            lflat = self._flat_device_arrays(lpart, "l.", consume=False)
            if lflat is None:
                return None
            if self._rflat_dev is None:
                rf = _leaf_flat_arrays(self.big, "r.")
                if rf is None:
                    return None
                # the device copy of the build side pins HBM for the
                # executor's lifetime: charge it against the handoff
                # budget once, up front
                rf_nb = sum(v.nbytes for v in rf.values())
                if rf_nb > getattr(self, "_handoff_left", 0):
                    return None
                self._handoff_left -= rf_nb
                self._rflat_dev = {k: jnp.asarray(v) for k, v in rf.items()}
            rflat = self._rflat_dev
            left = op.how == "left"

            def src_pairs(flat, side_tag, src_ci, ci_out):
                ps = []
                for k in flat:
                    core = k[2:]
                    srcpath, suf = core.rsplit("#", 1)
                    if not (srcpath == str(src_ci)
                            or srcpath.startswith(f"{src_ci}.")
                            or srcpath.startswith(f"{src_ci}#")):
                        continue
                    outpath = str(ci_out) + srcpath[len(str(src_ci)):]
                    outkey = {"d": outpath, "b": outpath + "#bytes",
                              "l": outpath + "#len",
                              "v": outpath + "#valid"}[suf]
                    ps.append((outkey, side_tag, k, suf))
                return ps

            pairs: list = []
            for ci_out, (side, src_ci, mo) in enumerate(entries):
                flat = lflat if side == "l" else rflat
                ps = src_pairs(flat, side, src_ci, ci_out)
                if mo and not any(ok == f"{ci_out}#valid"
                                  for ok, _, _, _ in ps):
                    ps.append((f"{ci_out}#valid", "r", None, "synth_v"))
                pairs.extend(ps)

            # structural check: the assembled keys must be exactly what a
            # host-materialized partition would stage (one executable for
            # handoff-fed and host-fed batches alike)
            leaf_types: dict = {}
            for ci, ct in enumerate(out_types):
                for pth, lt in C.flatten_type(ct, str(ci)):
                    leaf_types[pth] = lt
            expect: set = set()
            for pth, lt in leaf_types.items():
                expect.update(C.staged_keys_for_type(pth, lt))
            if expect != {ok for ok, _, _, _ in pairs}:
                return None

            b2 = C.bucket_size(m, self.backend.bucket_mode)
            est = b2
            for _, side_tag, sk, suf in pairs:
                if sk is None:
                    est += b2
                    continue
                a = (lflat if side_tag == "l" else rflat)[sk]
                est += (a.nbytes // max(1, int(a.shape[0]))) * b2
            if est * 2 > getattr(self, "_handoff_left", 0):
                return None
            self._handoff_left -= est * 2
            lpart.device_batch = None     # committed: release the one-shot

            lidx = np.zeros(b2, np.int64)
            lidx[:m] = plan["left_idx"]
            ridx = np.zeros(b2, np.int64)
            ridx[:m] = plan["build_rows"]
            hm = np.zeros(b2, np.bool_)
            hm[:m] = plan["has_match"]
            fkey = ("joinassemble", tuple(pairs), left)
            fn = self.backend.jit_cache.get_or_build(
                fkey, lambda: _build_assemble_fn(tuple(pairs), left))
            outs = fn(lflat, rflat, jnp.asarray(lidx), jnp.asarray(ridx),
                      jnp.asarray(hm))

            schema = T.row_of(out_cols, out_types)
            outp = C.Partition(schema=schema, num_rows=m, leaves={},
                               start_index=lpart.start_index)
            view = dict(outs)
            rv = np.zeros(b2, np.bool_)
            rv[:m] = True
            view["#rowvalid"] = jnp.asarray(rv)
            view["#seed"] = C.partition_seed(outp)

            def loader(pth):
                arrs = {}
                for k in C.result_keys_for_leaf(outs, pth):
                    h = np.asarray(jax.device_get(outs[k][:m]))
                    xferstats.note_d2h(h.nbytes, tag="lazy_load")
                    arrs[k] = h
                return C.leaf_from_result_arrays(arrs, pth,
                                                 leaf_types[pth], m)

            ll = C.LazyLeaves(leaf_types.keys(), loader, tag="join")
            ll.nbytes_hint = est
            outp.leaves = ll
            outp.device_batch = C.DeviceBatch(arrays=view, n=m, b=b2,
                                              schema=schema)
            return outp
        except Exception:   # pragma: no cover - purely an optimization
            return None

    def _match_positions(self, sig: np.ndarray):
        import numpy as _np

        from ..parallel import mesh as _mesh

        u = len(self.uniq_view)
        words = _pack_sig_words(sig)
        n = words.shape[0]
        b = C.bucket_size(n)
        n_dev = len(self._mesh.devices.flat) if self._mesh is not None else 1
        b = -(-b // n_dev) * n_dev
        if b > n:
            words = _np.concatenate(
                [words, _np.zeros((b - n, self._nw), _np.uint64)])
        fn = self.backend.jit_cache.get_or_build(
            ("joinprobe", u, self._nw, id(self._mesh)),
            lambda: _build_probe_fn(u, self._nw, self._mesh))
        pos, matched = fn(words, self._build_words)
        pos = _mesh.materialize_np(pos)[:n]
        matched = _mesh.materialize_np(matched)[:n]
        return pos, matched

    def _flat_device_arrays(self, part: C.Partition, side: str,
                            consume: bool = True):
        """Flat '#d/#b/#l/#v' gather inputs, preferring a device-resident
        handoff view over host leaves (the view's arrays skip both the
        D2H of the producing stage and the H2D here). Falls back to the
        host leaf arrays — forcing lazy leaves if it must.

        consume=False peeks without releasing the one-shot view — callers
        that may still bail to the host path must not burn it (a consumed
        view would force a full lazy-leaf D2H on the fallback)."""
        dv = getattr(part, "device_batch", None)
        if dv is not None and dv.n == part.num_rows:
            if consume:
                part.device_batch = None      # one-shot, like stage_partition
            out = {}
            for k, v in dv.arrays.items():
                if k in ("#rowvalid", "#seed"):
                    continue
                if k.endswith("#bytes"):
                    out[f"{side}{k[:-6]}#b"] = v
                elif k.endswith("#len"):
                    out[f"{side}{k[:-4]}#l"] = v
                elif k.endswith("#valid"):
                    out[f"{side}{k[:-6]}#v"] = v
                else:
                    out[f"{side}{k}#d"] = v
            return out
        return _leaf_flat_arrays(part, side)

    def _gather(self, part: C.Partition, idx: np.ndarray, valid_rows=None
                ) -> Optional[dict]:
        import numpy as _np

        from ..parallel import mesh as _mesh

        m = len(idx)
        if m == 0:
            return _gather_leaves(part, idx, valid_rows)
        side = "r." if part is self.big else "l."
        arrays = self._flat_device_arrays(part, side)
        if arrays is None:
            return _gather_leaves(part, idx, valid_rows)
        mb = C.bucket_size(m)
        idx_p = _np.zeros(mb, _np.int64)
        idx_p[:m] = idx
        hm = _np.zeros(mb, _np.bool_)
        hm[:m] = valid_rows if valid_rows is not None else True
        keys = tuple(sorted(arrays))
        left_join = valid_rows is not None
        fn = self.backend.jit_cache.get_or_build(
            ("joingather", side, keys, left_join),
            lambda: _build_gather_fn(
                keys if side == "l." else (),
                keys if side == "r." else (), left_join))
        if side == "l.":
            outs = fn(arrays, {}, idx_p, idx_p, hm)
        else:
            outs = fn({}, arrays, idx_p, idx_p, hm)
        outs = {k: _mesh.materialize_np(v) for k, v in outs.items()}
        # rebuild leaves, sliced back to the true match count. Leaf
        # structure derives from the SCHEMA + array key set, never from
        # leaf instances — the partition's host leaves may be lazy
        # (device-backed) and must not be forced here
        gathered: dict[str, C.Leaf] = {}
        for ci, ct in enumerate(part.schema.types):
            for path, _lt in C.flatten_type(ct, str(ci)):
                if f"{side}{path}#b" in outs:
                    b_ = _np.asarray(outs[f"{side}{path}#b"])[:m]
                    ln = _np.asarray(outs[f"{side}{path}#l"])[:m]
                    valid = _np.asarray(outs[f"{side}{path}#v"])[:m] \
                        if f"{side}{path}#v" in outs else None
                    if left_join and valid is None:
                        valid = hm[:m].copy()
                    gathered[path] = C.StrLeaf(b_, ln, valid)
                elif f"{side}{path}#d" in outs:
                    data = _np.asarray(outs[f"{side}{path}#d"])[:m]
                    valid = _np.asarray(outs[f"{side}{path}#v"])[:m] \
                        if f"{side}{path}#v" in outs else None
                    if left_join and valid is None:
                        valid = hm[:m].copy()
                    gathered[path] = C.NumericLeaf(data, valid)
                else:
                    gathered[path] = C.NullLeaf(m)
        return gathered
