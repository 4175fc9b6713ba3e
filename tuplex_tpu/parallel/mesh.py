"""Device-mesh execution of stage functions.

The TPU replacement for the reference's executor thread pool + (absent)
shuffle layer (reference: core/include/Executor.h WorkQueue;
SURVEY.md §2.10): partitions are row-sharded across a `jax.sharding.Mesh`
and the SAME fused stage function runs under pjit — row-wise pipelines
partition with zero collectives; aggregates/joins add psum/all_gather inside
the traced function (see parallel/collectives.py).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..runtime import tracing as TR
from ..runtime.columns import host_nbytes
from ..runtime.jaxcfg import jax, jnp

DATA_AXIS = "data"


def make_mesh(n_devices: Optional[int] = None, axis: str = DATA_AXIS):
    from jax.sharding import Mesh

    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]), (axis,))


def make_mesh_of(devices, axis: str = DATA_AXIS):
    """Mesh over an explicit (surviving) device list — the elastic
    partial-mesh rebuild path."""
    from jax.sharding import Mesh

    return Mesh(np.array(list(devices)), (axis,))


def row_sharding(mesh, axis: str = DATA_AXIS):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(axis))


def shard_layout(x) -> list:
    """[(device id, shard shape)] of a placed array — which device holds
    which piece (chip_smoke --chips 4 checks a staged batch is spread over
    the whole mesh, not parked on device 0)."""
    return [(s.device.id, tuple(s.data.shape))
            for s in x.addressable_shards]


def _named_mesh_fn(fn, raw_fn, tag: str):
    """`jit_tpx_mesh_<key8>`: the replicated-output wrapper of a stage fn,
    under the key8 its raw fn was named with (plan/physical)."""
    return TR.name_fn(fn, "mesh", TR.fn_key8(raw_fn, tag))


def shard_stage_fn(raw_fn, mesh, axis: str = DATA_AXIS, salt: str = "",
                   tag: str = "", n_ops: int = 0, deadline=None,
                   on_dispatch=None):
    """Compile a stage function with every leading-dim array row-sharded
    over the mesh. Row-wise stage bodies partition trivially (XLA inserts no
    collectives); reduction stages contain their own psums.

    Single-process (CI's virtual mesh, a single-host TPU slice): the batch
    is placed row-sharded on the mesh and the fn compiles through the
    content-addressed store (exec/compilequeue) for exactly that mesh —
    `salt` carries the backend's mesh epoch, and a second process loads the
    stored executable onto the same devices instead of compiling.
    `on_dispatch(placed, outs)` sees each call's device arrays.
    Multi-process (jax.distributed / DCN): each
    process stages ONLY ITS ROW RANGE of the batch
    (make_array_from_process_local_data — host-sharded staging, so H2D is
    1/P per host), and outputs are constrained to replicated so every
    process can materialize results host-side (np.asarray on a
    fully-replicated array is local)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    shard = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())     # 0-d scalars (e.g. '#seed'): replicate
    nproc = jax.process_count()

    if nproc == 1:
        from ..exec.compilequeue import aot_jit

        jfn = aot_jit(raw_fn, salt=salt, tag=tag, n_ops=n_ops,
                      deadline=deadline)

        def sharded(arrays):
            # the row-sharded placement, leaf by leaf: the upload of a
            # mesh dispatch (`xferstats` counts its bytes where the caller
            # staged them: exec/local._dispatch_launch, _general_case_pass)
            with TR.span("h2d:mesh-put", "xfer") as _sp:
                placed = {k: jax.device_put(v, shard if np.ndim(v) else repl)
                          for k, v in arrays.items()}
                if _sp is not TR.NOOP:
                    _sp.set("bytes", host_nbytes(arrays)) \
                        .set("leaves", len(arrays)) \
                        .set("devices", int(mesh.devices.size))
            outs = jfn(placed)
            if on_dispatch is not None:
                on_dispatch(placed, outs)
            return outs

        return sharded

    def replicated_out(arrays):
        out = raw_fn(arrays)
        return jax.tree.map(
            lambda o: jax.lax.with_sharding_constraint(o, repl), out)

    jfn = jax.jit(_named_mesh_fn(replicated_out, raw_fn, tag))
    pid = jax.process_index()

    def local_row_range(shape):
        """This process's contiguous row range under `shard` — derived from
        the sharding's own index map, NOT a uniform b/nproc split (devices
        need not spread evenly across processes, e.g. a 3-device mesh over
        2 hosts)."""
        los, his = [], []
        for d, idx in shard.devices_indices_map(shape).items():
            if d.process_index != pid:
                continue
            sl = idx[0]
            los.append(0 if sl.start is None else sl.start)
            his.append(shape[0] if sl.stop is None else sl.stop)
        if not los:
            return 0, 0     # no addressable mesh device on this process
        return min(los), max(his)

    def dispatch(arrays):
        placed = {}
        for k, v in arrays.items():
            if np.ndim(v) == 0:
                placed[k] = jax.device_put(v, repl)
                continue
            v = np.asarray(v)
            lo, hi = local_row_range(v.shape)
            placed[k] = jax.make_array_from_process_local_data(
                shard, np.ascontiguousarray(v[lo:hi]), v.shape)
        return jfn(placed)

    return dispatch


def hostblock_stage_fn(raw_fn, mesh, block_rows: int, axis: str = DATA_AXIS):
    """Multi-process dispatch where each process's LOCAL staged batch IS
    its shard: the global batch is [host0 block | host1 block | ...] with
    every block `block_rows` slots (tail-padded per host), assembled via
    make_array_from_process_local_data. block_rows must divide evenly
    over each process's devices. Outputs replicate (every host
    materializes the full result). Powers host-sharded reads
    (parallel/hostio): the data a process stages is only what IT read."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    shard = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())
    nproc = jax.process_count()

    def replicated_out(arrays):
        out = raw_fn(arrays)
        return jax.tree.map(
            lambda o: jax.lax.with_sharding_constraint(o, repl), out)

    jfn = jax.jit(_named_mesh_fn(replicated_out, raw_fn, "hostblock"))

    def dispatch(local_arrays):
        placed = {}
        for k, v in local_arrays.items():
            if np.ndim(v) == 0:
                placed[k] = jax.device_put(v, repl)
                continue
            v = np.ascontiguousarray(np.asarray(v))
            assert v.shape[0] == block_rows, (k, v.shape, block_rows)
            gshape = (block_rows * nproc,) + v.shape[1:]
            placed[k] = jax.make_array_from_process_local_data(
                shard, v, gshape)
        return jfn(placed)

    return dispatch


def materialize_np(x) -> np.ndarray:
    """Host-materialize a mesh output. Single-process (or replicated /
    fully-addressable) arrays convert directly; under jax.distributed a
    row-sharded output spans other processes' devices, so gather it
    (process_allgather over DCN) first."""
    if jax.process_count() == 1:
        return np.asarray(x)
    if not hasattr(x, "sharding") or x.is_fully_replicated \
            or x.is_fully_addressable:
        return np.asarray(x)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(x, tiled=True))


def pad_batch_for_mesh(arrays: dict, n_devices: int) -> dict:
    """Pad the leading dim to a multiple of the mesh size (XLA requires
    divisible sharding)."""
    b = arrays["#rowvalid"].shape[0]
    target = -(-b // n_devices) * n_devices
    if target == b:
        return arrays
    out = {}
    with TR.span("mesh:pad-batch", "xfer") as _sp:
        _sp.set("rows", b).set("batch", target)
        for k, v in arrays.items():
            if np.ndim(v) == 0:         # scalars (e.g. '#seed') replicate
                out[k] = v
                continue
            pad = [(0, target - b)] + [(0, 0)] * (v.ndim - 1)
            out[k] = np.pad(np.asarray(v), pad)
    return out
