"""Bridging columnar batches <-> emitter CVs and building fused stage fns.

This is the TransformStage/StageBuilder analog (reference:
core/src/physical/StageBuilder.cc generateFastCodePath — assembles the fused
per-row pipeline; here we assemble a fused per-BATCH jax function that the
backend jits once per (stage, schema, bucket-spec)).
"""

from __future__ import annotations

from typing import Any, Optional

from ..core import typesys as T
from ..core.errors import NotCompilable
from ..runtime.jaxcfg import jnp
from .values import CV, tuple_cv


def require_traceable(ops, speculate: bool = True) -> None:
    """Consume the plan-time traceability verdict (compiler/analyzer.py):
    raise NotCompilable BEFORE any emitter work when a fused UDF is
    statically known untraceable. With `speculate` on, findings inside
    if-arms are left to the trace (branch pruning may remove them)."""
    from .analyzer import op_analysis

    for op in ops:
        rep = op_analysis(op)
        f = rep.routing_finding(speculate) if rep is not None else None
        if f is not None:
            raise NotCompilable(
                f"UDF {rep.name} statically untraceable: {f.reason} "
                f"({rep.loc(f)})")


def partition_avals(part, bucket_mode: str = "q8", rows=None):
    """Abstract (ShapeDtypeStruct) mirror of ``columns.stage_partition``
    for `part` — the exact avals its dispatch batch will have, computed
    without copying a byte. Feeds the ahead-of-time compile pool
    (exec/compilequeue): compiling against these avals means the real
    dispatch finds its executable already built. None when a leaf has no
    device layout. `rows` stands in for ``part.num_rows`` where `part` is
    a `PartitionStream`'s template and the partition is not built yet."""
    import numpy as np

    from ..runtime import columns as C
    from ..runtime.jaxcfg import jax

    b = C.bucket_size(part.num_rows if rows is None else rows, bucket_mode)
    avals: dict = {}
    for path, leaf in part.leaves.items():
        ks = C._leaf_keys(path, leaf)
        if ks is None:
            return None                     # host-only ObjectLeaf
        if not ks:
            continue                        # NullLeaf: layout-free
        if isinstance(leaf, C.NumericLeaf):
            avals[path] = jax.ShapeDtypeStruct((b,), leaf.data.dtype)
        else:
            wb = C.bucket_size(max(leaf.width, 1), bucket_mode, minimum=8)
            avals[path + "#bytes"] = jax.ShapeDtypeStruct((b, wb), np.uint8)
            avals[path + "#len"] = jax.ShapeDtypeStruct(
                (b,), leaf.lengths.dtype)
        if path + "#valid" in ks:
            avals[path + "#valid"] = jax.ShapeDtypeStruct((b,), np.bool_)
    avals["#rowvalid"] = jax.ShapeDtypeStruct((b,), np.bool_)
    avals["#seed"] = jax.ShapeDtypeStruct((), np.uint32)
    return avals


def restage_avals(out_avals: dict, bucket_mode: str = "q8"):
    """Predicted input avals of the NEXT stage, given this stage's
    ``jax.eval_shape`` output avals: control keys drop, data keys re-stage
    at the same batch size (exact when every input row emits one output
    row — the chain stops at filters/limits upstream), and str widths
    re-bucket from the TRACE width (partition_from_result_arrays keeps the
    device array's byte width, so the next staging pads to
    bucket(trace_width) — predictable without looking at content). None
    when the layout can't be predicted (compacted outputs, structural
    markers)."""
    from ..runtime import columns as C
    from ..runtime.jaxcfg import jax

    import numpy as np

    if "#rowidx" in out_avals:
        return None        # compaction: output batch size is data-dependent
    avals: dict = {}
    b = None
    for k, v in out_avals.items():
        if k.startswith("#"):
            continue       # '#err'/'#keep'/fold lattice: not re-staged
        if k.endswith(("#null", "#unit", "#opt")):
            return None    # structural markers re-stage under other keys
        if k.endswith("#bytes"):
            wb = C.bucket_size(max(int(v.shape[1]), 1), bucket_mode,
                               minimum=8)
            avals[k] = jax.ShapeDtypeStruct((v.shape[0], wb), v.dtype)
        else:
            avals[k] = jax.ShapeDtypeStruct(v.shape, v.dtype)
        b = int(v.shape[0])
    if not avals or b is None:
        return None
    avals["#rowvalid"] = jax.ShapeDtypeStruct((b,), np.bool_)
    avals["#seed"] = jax.ShapeDtypeStruct((), np.uint32)
    return avals


def leaf_cv(arrays: dict, path: str, t: T.Type) -> CV:
    """CV view over a staged leaf (see runtime.columns.stage_partition)."""
    base = t.without_option() if t.is_optional() else t
    opt = t.is_optional()
    valid = arrays.get(path + "#valid") if opt else None
    if isinstance(base, T.TupleType):
        elts = []
        if opt:
            tvalid = arrays[path + "#opt"]
            valid = tvalid if valid is None else valid & tvalid
        for i, e in enumerate(base.elements):
            elts.append(leaf_cv(arrays, f"{path}.{i}", T.option(e) if opt else e))
        return tuple_cv(elts, valid=valid)
    if base is T.STR:
        return CV(t=t, sbytes=arrays[path + "#bytes"], slen=arrays[path + "#len"],
                  valid=valid)
    if base is T.NULL:
        return CV(t=T.NULL, const=None)
    if base is T.EMPTYTUPLE:
        return tuple_cv([], valid=valid)
    if base in (T.BOOL, T.I64, T.F64):
        return CV(t=t, data=arrays[path], valid=valid)
    raise NotCompilable(f"column type {t} has no device layout")


def input_row_cv(arrays: dict, schema: T.RowType) -> CV:
    """The row value passed to the first UDF: single unnamed column -> bare
    value; otherwise a named row tuple (dict-style access resolves on names)."""
    from ..runtime.columns import user_columns

    cvs = [leaf_cv(arrays, str(i), t) for i, t in enumerate(schema.types)]
    cols = user_columns(schema)
    if len(cvs) == 1 and cols is None:
        return cvs[0]
    return tuple_cv(cvs, names=cols)


def result_arrays(cv: CV, b: int) -> tuple[dict, T.Type]:
    """Flatten a stage RESULT into row-layout arrays: a plain tuple result
    spreads into columns 0..k-1; anything else is the single column 0 (same
    convention as runtime.columns.schema_for_result_type)."""
    from .values import materialize

    cv = materialize(cv, b) if cv.is_const else cv

    def _has_list(v) -> bool:
        if v.kind in ("list", "genexp"):
            return True
        return v.elts is not None and any(_has_list(e) for e in v.elts)

    if _has_list(cv):
        # list/generator results must keep python's types: interpreter path
        from ..core.errors import NotCompilable

        raise NotCompilable("list-valued result")
    if cv.elts is not None and cv.valid is None:
        out: dict[str, Any] = {}
        for i, e in enumerate(cv.elts):
            sub, _ = cv_output_arrays(e, b, str(i))
            out.update(sub)
        return out, cv.t
    return cv_output_arrays(cv, b, "0")


def cv_output_arrays(cv: CV, b: int, prefix: str = "") -> tuple[dict, T.Type]:
    """Flatten a result CV into named output arrays + its row-able type.

    Output keys mirror the staged-input convention so results can be rebuilt
    into Partitions (runtime.columns layout).
    """
    from .values import materialize

    cv = materialize(cv, b) if cv.is_const else cv
    out: dict[str, Any] = {}
    t = cv.t
    base = cv.base
    if cv.elts is not None:
        opt = cv.valid is not None
        if opt:
            out[prefix + "#opt"] = cv.valid
        if not cv.elts:  # empty tuple: keep a structural marker
            out[prefix + "#unit"] = jnp.zeros(b, dtype=bool)
            et = T.EMPTYTUPLE
            return out, (T.option(et) if opt else et)
        ts = []
        for i, e in enumerate(cv.elts):
            sub, et = cv_output_arrays(e, b, f"{prefix}.{i}" if prefix else str(i))
            out.update(sub)
            ts.append(et)
        tt = T.tuple_of(*ts)
        return out, (T.option(tt) if opt else tt)
    if base is T.STR:
        out[prefix + "#bytes"] = cv.sbytes
        out[prefix + "#len"] = cv.slen
        if cv.valid is not None:
            out[prefix + "#valid"] = cv.valid
        return out, t
    if base is T.NULL:
        # structural marker so the column survives the round trip
        out[prefix + "#null"] = jnp.zeros(b, dtype=bool)
        return out, T.NULL
    if base is T.EMPTYTUPLE:
        out[prefix + "#unit"] = jnp.zeros(b, dtype=bool)
        if cv.valid is not None:
            out[prefix + "#valid"] = cv.valid
        return out, t
    if base in (T.BOOL, T.I64, T.F64):
        out[prefix] = cv.data
        if cv.valid is not None:
            out[prefix + "#valid"] = cv.valid
        return out, t
    raise NotCompilable(f"output type {t} has no columnar layout")
