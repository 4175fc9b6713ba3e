"""Multi-device / multi-host backend.

The distributed seam of the reference is IBackend (reference:
core/include/ee/IBackend.h:29-45; AwsLambdaBackend.cc fans tasks out over
Lambda with S3 as the data plane). The TPU-native replacement: the SAME fused
stage functions run under jit over a `jax.sharding.Mesh` — rows sharded
across devices on the data axis, XLA inserting collectives only where a
stage contains reductions. Multi-host: initialize `jax.distributed` before
building the Context and every host runs the same program (SPMD); DCN
carries the collectives, the driver host owns planning and host-side IO.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..core.errors import NotCompilable
from ..parallel import mesh as M
from .local import LocalBackend


class MultiHostBackend(LocalBackend):
    """LocalBackend whose device dispatch row-shards every batch over a mesh.

    Usable single-process with N local devices (CI: 8 virtual CPU devices)
    and unchanged under multi-host jax.distributed initialization.
    """

    # selection-vector compaction computes a global nonzero() over the batch;
    # under shard_map that would need a cross-device exchange to stay
    # load-balanced, so the mesh path keeps full-length outputs
    supports_compaction = False
    # fused fold partials are scalar outputs the shard_map wrapper's
    # out_specs don't carry; the mesh fold path (psum over ICI) handles
    # aggregation instead
    supports_fused_fold = False
    dispatch_path = "mesh"

    def __init__(self, options):
        super().__init__(options)
        import jax

        shape = options.get_str("tuplex.tpu.meshShape", "auto")
        n = len(jax.devices()) if shape == "auto" else int(shape.split("x")[0])
        self.mesh = M.make_mesh(n)
        self.n_devices = n
        self._mesh_epoch = 0    # bumped on elastic shrink
        # in one process a small violation set leaves the mesh for the
        # host-CPU executable, as on one chip; across processes every
        # dispatch stays SPMD
        self.host_resolve = jax.process_count() == 1
        self.shard_layout: dict = {}    # see _note_shards
        # span streams key their pid lane by the HOST (jax process index)
        # so per-host dumps merge into one driver timeline without
        # colliding; single-process runs keep the default OS pid
        if jax.process_count() > 1:
            from ..runtime import tracing

            tracing.set_host(jax.process_index())

    def fn_cache_salt(self) -> str:
        """Stage-fn cache keys must change when the mesh does — a cached fn
        closes over the mesh's device set, and a post-shrink fetch of a
        pre-shrink fn would dispatch onto the dead device forever."""
        return f"/mesh{self._mesh_epoch}x{self.n_devices}"

    def _surviving_devices(self) -> list:
        """Probe every mesh device with a tiny put+compute round trip; the
        survivors define the reduced mesh. (A wedged — as opposed to
        erroring — device is indistinguishable from a slow one without a
        deadline; the reference's Lambda analog has the same blind spot and
        bounds it with request timeouts.)"""
        import jax
        import numpy as np

        alive = []
        for d in self.mesh.devices.flat:
            try:
                x = jax.device_put(np.ones(8, dtype=np.float32), d)
                (x + 1).block_until_ready()
                alive.append(d)
            except Exception:
                continue
        return alive

    def _elastic_stage_fn(self, stage, skey, in_schema):
        """Elastic degrade ladder for a twice-failed mesh dispatch (lost
        device, wedged collective) — reference analog: AWSLambdaBackend
        re-invokes failed tasks at full remaining concurrency:

        1. REDUCED MESH: rebuild over the devices that still answer a
           probe and re-shard the same stage over them (padding adapts —
           any size >= 2 works, not just pow2). Later stages of the job
           ride the smaller mesh too.
        2. Single device, plain jit.
        3. (caller) interpreter.
        """
        import jax

        try:
            raw = stage.build_device_fn(
                in_schema, compaction=False,
                fused_fold=self.supports_fused_fold)
        except Exception:
            return None
        alive = self._surviving_devices()
        if jax.process_count() == 1 and 2 <= len(alive) < self.n_devices:
            try:
                new_mesh = M.make_mesh_of(alive)
                prev_mesh, prev_n = self.mesh, self.n_devices
                # _jit_stage_fn reads self.mesh/n_devices; commit only
                # after the fn builds (a failed build must not leave a
                # shrunk-but-unvalidated mesh or a false log entry)
                self.mesh, self.n_devices = new_mesh, len(alive)
                try:
                    fn = self.jit_cache.get_or_build(
                        ("elastic-mesh", skey, len(alive)),
                        lambda: self._jit_stage_fn(raw))
                except Exception:
                    self.mesh, self.n_devices = prev_mesh, prev_n
                    raise
                self._mesh_epoch += 1   # invalidate mesh-keyed fn caches
                self.failure_log.append({
                    "stage": skey[:16], "action": "elastic-mesh",
                    "devices": len(alive)})
                return fn
            except Exception:
                pass
        return self.jit_cache.get_or_build(
            ("elastic", skey), lambda: jax.jit(raw))

    def _jit_stage_fn(self, raw_fn, packed: bool = True, tag: str = "",
                      n_ops: int = 0):
        """Row-shard over ALL mesh devices (`packed` is accepted for
        interface parity and ignored: mesh staging is per-leaf sharded
        device_put). Single-process meshes compile through the AOT store
        keyed on the mesh epoch (fn_cache_salt): the artifact records the
        mesh's device ids and a later process loads it onto those same
        devices. Non-pow2 meshes work too: the
        batch pads up to a multiple of the mesh size before dispatch (padded
        rows carry #rowvalid=False and the host slices outputs back to the
        partition's row count) — round 1 silently rounded 6 devices down to
        4 and kept a dead pow2 raise here."""
        inner = M.shard_stage_fn(
            raw_fn, self.mesh, salt=self.fn_cache_salt(), tag=tag,
            n_ops=n_ops,
            deadline=self.options.get_float("tuplex.tpu.compileDeadlineS",
                                            0.0),
            on_dispatch=self._note_shards)
        n_dev = self.n_devices

        def padded_dispatch(arrays):
            return inner(M.pad_batch_for_mesh(arrays, n_dev))

        return padded_dispatch

    def _note_shards(self, placed, outs) -> None:
        """Where the largest dispatch so far kept its batch: per-device
        shard shapes of one staged input and one output (metadata only;
        callers reset ``shard_layout`` to {} to start a new window)."""
        if "#rowvalid" not in placed or "#err" not in outs:
            return
        rows = placed["#rowvalid"].shape[0]
        if rows > self.shard_layout.get("rows", 0):
            self.shard_layout = {
                "rows": rows,
                "input": M.shard_layout(placed["#rowvalid"]),
                "output": M.shard_layout(outs["#err"])}

    # -- host-sharded reads (each process staged ONLY its byte range) ------
    def execute(self, stage, partitions, intermediate: bool = False,
                box_output: bool = False):
        import itertools

        it = iter(partitions or [])
        first = next(it, None)
        if first is not None and \
                getattr(first, "host_block", None) is not None:
            rest = list(it)
            assert not rest, "host-block sources produce one partition"
            from ..runtime import tracing as TR

            with TR.span("hostblock:execute", "exec") as _sp:
                res = self._execute_hostblock(stage, first)
                if _sp is not TR.NOOP:
                    _sp.set("key", stage.key()[:12])
                    _sp.set("rows_out", res.metrics.get("rows_out", 0))
            return res
        parts = [] if first is None else itertools.chain([first], it)
        return super().execute(stage, parts, intermediate=intermediate,
                               box_output=box_output)

    def _execute_hostblock(self, stage, part):
        """Transform-stage execution over a host-sharded source: the global
        batch is [host0 block | host1 block | ...] (each block tail-padded
        to the same slot count), devices hold exactly the rows their host
        READ, outputs replicate, and rows needing the interpreter resolve
        on the host that owns their raw data with the boxed results
        exchanged over DCN (reference analog: workers read their own S3
        ranges and ship exception rows back, AWSLambdaBackend.cc:410-506;
        here the exchange is an allgather). The compiled general tier runs
        HOST-LOCALLY (plain jit over each host's own err rows) before the
        interpreter, same ladder as the local backend."""
        import time

        import jax

        from ..parallel.hostio import allgather_obj
        from ..runtime import columns as C
        from ..runtime import tracing as TR
        from .local import ExceptionRecord, StageResult

        t0 = time.perf_counter()
        hb = part.host_block
        pid, nproc, counts = hb["pid"], hb["nproc"], hb["counts"]
        total = sum(counts)
        metrics: dict = {"fast_path_s": 0.0, "slow_path_s": 0.0,
                         "general_path_s": 0.0, "compile_s": 0.0}
        if total == 0:
            return StageResult([], [], metrics)
        # per-host slot count: every block identical, divisible over each
        # process's local devices (q8 widths are multiples of 8; device
        # counts per host are too on real pods — round up to be safe)
        ldev = max(1, self.n_devices // nproc)
        quant = 8 * ldev
        bh = -(-max(max(counts), 1) // quant) * quant
        # GLOBAL shape agreement: string widths differ per host's data
        local_w = {p: C.bucket_size(max(leaf.width, 1), self.bucket_mode,
                                    minimum=8)
                   for p, leaf in part.leaves.items()
                   if isinstance(leaf, C.StrLeaf)}
        mask_list = None if part.normal_mask is None \
            else part.normal_mask.tolist()
        with TR.span("hostblock:shape-exchange", "exec"):
            meta = allgather_obj({"w": local_w, "mask": mask_list})
        fw = {p: max(m["w"].get(p, 8) for m in meta) for p in local_w}

        # ---- compiled fast path over the assembled global batch ----------
        skey = stage.key() + "/" + part.schema.name + "/hostblock" \
            + self.fn_cache_salt()
        out_arrays: dict = {}
        err = keep = None
        if not self.interpret_only and skey not in self._not_compilable:
            try:
                with TR.span("hostblock:fastpath", "exec") as _fsp:
                    _fsp.set("slots", bh * nproc)
                    fn = self.jit_cache.get_or_build(
                        ("stagefn", skey, bh),
                        lambda: M.hostblock_stage_fn(
                            stage.build_device_fn(
                                part.schema, compaction=False,
                                fused_fold=False),
                            self.mesh, bh))
                    batch = C.stage_partition(part, self.bucket_mode,
                                              force_b=bh, force_widths=fw)
                    # replicated scalars must be IDENTICAL across processes
                    # (device_put asserts it): the per-host seed derives
                    # from the host-local start_index — use the global
                    # block's
                    batch.arrays["#seed"] = C.partition_seed(
                        C.Partition(schema=part.schema, num_rows=0,
                                    start_index=0))
                    outs = fn(batch.arrays)
                    outs = {k: M.materialize_np(v) for k, v in outs.items()}
                    err = outs.pop("#err")
                    keep = outs.pop("#keep")
                    out_arrays = outs
            except NotCompilable:
                self._not_compilable.add(skey)
        metrics["fast_path_s"] = time.perf_counter() - t0

        # global slot validity: [h*bh, h*bh + counts[h]) minus each host's
        # boxed (normal_mask False) rows
        nslots = bh * nproc
        slot_normal = np.zeros(nslots, dtype=bool)
        for h in range(nproc):
            m = meta[h]["mask"]
            blk = slice(h * bh, h * bh + counts[h])
            slot_normal[blk] = True if m is None else np.asarray(m, bool)
        if err is not None:
            compiled_ok = slot_normal & keep[:nslots] & (err[:nslots] == 0)
            my_err = slot_normal & (err[:nslots] != 0)
        else:
            compiled_ok = np.zeros(nslots, dtype=bool)
            my_err = slot_normal.copy()
        # rows THIS host must interpret: its err slots + its boxed rows.
        # take(n): resolution work is bounded to slots before the point
        # where compiled rows alone satisfy the limit (the exchange below
        # still runs exactly once on every process — SPMD lockstep)
        cutoff = nslots
        if stage.limit >= 0:
            cum = np.cumsum(compiled_ok)
            hit = np.nonzero(cum >= stage.limit)[0]
            if hit.size:
                cutoff = int(hit[0]) + 1
        lo = pid * bh
        local_fb = [i for i in range(counts[pid])
                    if lo + i < cutoff and (
                        my_err[lo + i] or not (
                            part.normal_mask is None
                            or part.normal_mask[i]))]

        # ---- compiled general tier on the OWNING host --------------------
        # (same ladder as the local backend: supertype re-trace first,
        # interpreter only for rows the general tier neither resolved nor
        # FILTERED — its filter verdicts are final, like the local
        # backend's; each host runs over ITS OWN rows and the results ride
        # the same exchange). device_codes prunes rows whose fast-path
        # code is already an exact Python exception class.
        resolved_local: dict = {}
        fb_set = set(local_fb)
        if fb_set and not self.interpret_only \
                and stage.resolve_plan().use_general:
            from ..core.errors import unpack_device_codes

            dc = {}
            if err is not None:
                import numpy as _np

                codes = stage.op_ids_of_lattice(
                    _np.asarray(err)[_np.asarray(local_fb) + lo])
                dc = dict(zip(local_fb, unpack_device_codes(codes)))
            t1 = time.perf_counter()
            try:
                with TR.span("resolve:general", "exec") as _gsp:
                    _gsp.set("rows", len(fb_set)).set("tier", "host-local")
                    self._general_case_pass(stage, part, fb_set,
                                            resolved_local, device_codes=dc,
                                            local_jit=True, span=_gsp)
            except Exception as e:
                from ..utils.logging import get_logger

                get_logger("exec").warning(
                    "host-local general tier failed (%s: %s); rows stay "
                    "on the interpreter", type(e).__name__, e)
                resolved_local = {}
                fb_set = set(local_fb)
            metrics["general_path_s"] = time.perf_counter() - t1

        # ---- interpreter on the OWNING host + result exchange ------------
        t1 = time.perf_counter()
        payload = [(lo + i, "ok", row) for i, row in resolved_local.items()]
        local_fb = [i for i in local_fb
                    if i in fb_set and i not in resolved_local]
        if local_fb:
            with TR.span("resolve:interpreter", "exec") as _isp:
                _isp.set("rows", len(local_fb))
                pipeline = stage.python_pipeline(part.user_columns)
                for i, row in zip(local_fb, C.decode_rows(part, local_fb)):
                    status, pl = pipeline(row)
                    payload.append((lo + i, status, pl))
        resolved: dict = {}
        exc_by_slot: dict = {}
        with TR.span("hostblock:resolve-exchange", "exec") as _xsp:
            _xsp.set("sent", len(payload))
            for host_payload in allgather_obj(payload):
                for slot, status, pl in host_payload:
                    if status == "ok":
                        resolved[slot] = pl
                    elif status == "exc":
                        exc_by_slot[slot] = ExceptionRecord(
                            pl[0], pl[1], pl[2],
                            pl[3] if len(pl) > 3 else None)
        metrics["slow_path_s"] = time.perf_counter() - t1

        pseudo = C.Partition(schema=part.schema, num_rows=nslots,
                             leaves={}, start_index=0)
        outp = self._merge(stage, pseudo, compiled_ok, out_arrays, resolved)
        self.mm.register(outp)
        exceptions = [exc_by_slot[s] for s in sorted(exc_by_slot)]
        metrics["rows_out"] = outp.num_rows
        return StageResult([outp], exceptions, metrics)


def init_multihost(coordinator_address: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None) -> None:
    """Initialize jax.distributed for multi-host execution (reference analog:
    AwsLambdaBackend bring-up; here DCN + the JAX runtime replace the
    Invoke/S3 control+data planes)."""
    import jax

    kwargs = {}
    if coordinator_address:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)
