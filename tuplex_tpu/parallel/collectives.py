"""Mesh-parallel reductions: the ICI collective layer.

The reference combines per-thread aggregates on the driver
(reference: LocalBackend.cc:911-919 thread-local tables + 2219
createFinalHashmap). On a mesh the same associative-combine contract becomes
XLA collectives: every device folds its row shard, then `psum`/`pmin`/`pmax`
over the data axis combines partials ON THE INTERCONNECT — no host
round-trip (SURVEY §2.10 item 5: "segment-reduce on device + psum over ICI").
"""

from __future__ import annotations

from typing import Callable, Sequence

import numpy as np

from ..runtime.jaxcfg import jax, jnp
from .mesh import DATA_AXIS

_BIG = 1 << 62


def reduce_identity(reducer: str, is_float: bool):
    """Neutral element per reducer — single source of truth shared with the
    host-side merge (exec/aggexec)."""
    if reducer == "sum":
        return 0.0 if is_float else 0
    if reducer == "min":
        return float("inf") if is_float else _BIG
    return float("-inf") if is_float else -_BIG


def _ident_arr(reducer: str, dtype):
    return jnp.asarray(
        reduce_identity(reducer, jnp.issubdtype(dtype, jnp.floating)), dtype)


def _batch_specs(arrays_example, axis):
    """Row-shard every batched array; replicate 0-d scalars ('#seed')."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    return {k: P(axis) if np.ndim(v) else P()
            for k, v in arrays_example.items()}


def _shape_key(arrays_example) -> tuple:
    """The builder's array argument as a process-stable key part."""
    return tuple(sorted((k, tuple(np.shape(v)), str(v.dtype))
                        for k, v in arrays_example.items()))


def sharded_fold_fn(eval_exprs: Callable, reducers: Sequence[str], mesh,
                    arrays_example, axis: str = DATA_AXIS):
    """Build a jitted mesh-parallel fold (ONE compile per cache entry: the
    returned callable has stable identity — cache it per stage/shape).

    eval_exprs(arrays) -> (list_of_[B]_value_arrays, ok_mask[B]) — the
    emitter-traced fold expressions (same trace as the single-chip path).
    Each device reduces its row shard locally, then combines with psum/
    pmin/pmax over the mesh axis; the result replicates on every device.
    """
    from jax.sharding import PartitionSpec as P

    from ..runtime import tracing as TR
    from ..runtime.jaxcfg import shard_map_compat

    def local_fold(arrays):
        vals, ok = eval_exprs(arrays)
        outs = []
        for v, red in zip(vals, reducers):
            masked = jnp.where(ok, v, _ident_arr(red, v.dtype))
            if red == "sum":
                outs.append(jax.lax.psum(masked.sum(), axis))
            elif red == "min":
                outs.append(jax.lax.pmin(masked.min(), axis))
            else:
                outs.append(jax.lax.pmax(masked.max(), axis))
        # ok mask travels back row-sharded so the host can route err rows to
        # the interpreter fold
        return tuple(outs) + (ok,)

    with TR.span("collective:build-fold", "compile") as _sp:
        _sp.set("reducers", list(reducers))
        specs = _batch_specs(arrays_example, axis)
        sharded = shard_map_compat(local_fold, mesh, (specs,),
                                   tuple(P() for _ in reducers) + (P(axis),))

        def fn(arrays):
            return sharded(arrays)

        return jax.jit(TR.name_fn(fn, "fold", TR.key8(
            tuple(reducers), _shape_key(arrays_example),
            tuple(mesh.devices.shape))))


def sharded_segment_fold_fn(eval_exprs: Callable, reducers: Sequence[str],
                            nseg: int, mesh, arrays_example,
                            axis: str = DATA_AXIS):
    """Mesh-parallel aggregateByKey: per-device segment reduction over local
    rows, then psum/pmin/pmax of the [nseg] partial tables across the mesh
    (the shuffle-free grouped aggregate: key codes are global, partial
    tables combine on ICI)."""
    from jax.sharding import PartitionSpec as P

    from ..runtime.jaxcfg import shard_map_compat

    def local_fold(arrays, codes):
        vals, ok = eval_exprs(arrays)
        outs = []
        for v, red in zip(vals, reducers):
            masked = jnp.where(ok, v, _ident_arr(red, v.dtype))
            if red == "sum":
                seg = jax.ops.segment_sum(masked, codes,
                                          num_segments=nseg + 1)
                outs.append(jax.lax.psum(seg, axis))
            elif red == "min":
                seg = jax.ops.segment_min(masked, codes,
                                          num_segments=nseg + 1)
                outs.append(jax.lax.pmin(seg, axis))
            else:
                seg = jax.ops.segment_max(masked, codes,
                                          num_segments=nseg + 1)
                outs.append(jax.lax.pmax(seg, axis))
        # per-segment ok counts: the host skips creating groups whose rows
        # ALL failed (ghost-group guard), + the ok mask for err routing
        counts = jax.lax.psum(
            jax.ops.segment_sum(ok.astype(jnp.int32), codes,
                                num_segments=nseg + 1), axis)
        return tuple(outs) + (counts, ok)

    from ..runtime import tracing as TR

    with TR.span("collective:build-segment-fold", "compile") as _sp:
        _sp.set("reducers", list(reducers)).set("nseg", nseg)
        specs = _batch_specs(arrays_example, axis)
        sharded = shard_map_compat(
            local_fold, mesh, (specs, P(axis)),
            tuple(P() for _ in reducers) + (P(), P(axis)))

        def fn(arrays, codes):
            return sharded(arrays, codes)

        return jax.jit(TR.name_fn(fn, "segfold", TR.key8(
            tuple(reducers), nseg, _shape_key(arrays_example),
            tuple(mesh.devices.shape))))
