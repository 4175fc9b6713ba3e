"""Local backend: dual-mode stage execution on one host.

Re-designs the reference's LocalBackend orchestration (reference:
core/src/ee/local/LocalBackend.cc:815-1253 executeTransformStage — JIT the
stage, run tasks per partition, route exception rows through the slow path,
merge in order :1254-1530 resolveViaSlowPath) for the TPU model:

  * the compiled fast path is ONE jax.jit executable per
    (stage-key, batch-spec) — cached like the reference's JITCompiler cache
  * rows whose device error code != 0 (or that were fallback slots already)
    re-run on the interpreter pipeline with resolvers (ResolveTask analog)
  * merge-in-order is positional: partitions preserve original row slots
"""

from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from ..core import typesys as T
from ..core.errors import (ExceptionCode, NotCompilable, TuplexException,
                           code_for_exception, exception_class_for_code,
                           exception_name, unpack_device_code,
                           unpack_device_codes)
from ..core.row import Row
from ..plan import logical as L
from ..plan.physical import TransformStage
from .compilequeue import CompileTimeout
from ..runtime import columns as C
from ..runtime import devprof as DP
from ..runtime import excprof as EX
from ..runtime import faults
from ..runtime import tracing as TR
from ..runtime import xferstats
from ..runtime.jaxcfg import jax as _jax, jnp as _jnp
from ..runtime.packing import PackedOuts, PackedStageFn


def _named_take(site: str):
    """A row gather over one stage output, `jnp.take(a, idx, axis=0)`,
    jitted under the name of its site: its module reads
    `jit_tpx_take_<site>` on the device, where eager calls all read
    `jit__take`. One launch a leaf, as the eager call was."""
    def take(a, idx):
        return _jnp.take(a, idx, axis=0)

    take.__name__ = take.__qualname__ = f"tpx_take_{site}"
    return _jax.jit(take)


take_view = _named_take("view")    # _attach_device_view's handoff view
take_lazy = _named_take("lazy")    # _lazy_merge's handoff view
take_load = _named_take("load")    # a LazyLeaves load of a lazy merge


def _get_outs(pending):
    """Materialize a stage result to host numpy: packed single-buffer
    fetch (runtime/packing.py) or plain per-leaf device_get."""
    import jax

    if isinstance(pending, PackedOuts):
        return pending.to_host()      # notes its own d2h bytes
    with TR.span("d2h:leaf-fetch", "xfer") as _sp:
        if _sp is not TR.NOOP:
            _note_shards_fetched(_sp, pending)
        outs = jax.device_get(pending)
        try:
            vals = outs.values() if isinstance(outs, dict) else outs
            nb = sum(np.asarray(v).nbytes for v in vals)
            xferstats.note_d2h(nb, tag="leaf_fetch")
            _sp.set("bytes", nb)
        except Exception:   # pragma: no cover - accounting is best-effort
            pass
    return outs


def _note_shards_fetched(sp, pending) -> None:
    """`d2h:leaf-fetch` of row-sharded outputs (the mesh backend's): how
    many devices the fetch gathers from and how many shards in all."""
    vals = list(pending.values()) if isinstance(pending, dict) else pending
    shards = devices = 0
    for v in vals:
        sh = getattr(v, "sharding", None)
        if sh is None or len(sh.device_set) < 2:
            continue
        devices = max(devices, len(sh.device_set))
        if not v.is_fully_replicated:
            shards += len(v.addressable_shards)
    if shards:
        sp.set("shards", shards).set("devices", devices)


def _all_ready(arrays: dict) -> bool:
    """Whether every device array of `arrays` is ready now (no wait)."""
    return all(v.is_ready() for v in arrays.values()
               if hasattr(v, "is_ready"))


def _note_view(sp, arrays: dict) -> None:
    """`handoff:view`: the leaves gathered and their device bytes."""
    if sp is not TR.NOOP:
        sp.set("leaves", len(arrays)).set(
            "bytes", sum(int(v.nbytes) for v in arrays.values()))


def _cpu_device():
    """The host CPU device alongside an accelerator backend, or None."""
    import jax

    try:
        return jax.local_devices(backend="cpu")[0]
    except Exception:
        return None


def _host_cpu_beside_accelerator() -> bool:
    """The default backend is an accelerator and the host CPU is a jax
    device beside it: where a host-pinned executable (_CpuJit) is a
    different place to run than the default one."""
    import jax

    return jax.default_backend() != "cpu" and _cpu_device() is not None


class _CpuJit:
    """jit pinned to the host CPU backend: numpy args placed (and the
    executable compiled) on the CPU device regardless of the default
    accelerator — used for small resolve batches where the device
    round-trip tax exceeds the compute, and for the deadline ladder's
    'cpu' tier.

    Per-input-spec compilation routes through exec/compilequeue's
    ``compile_traced`` (traced/lowered/compiled INSIDE the cpu
    default_device pin), so these host compiles are counted into the
    stage's ``compile_s``/``stage_compiles``, content-address-cached and
    reused like any other stage executable — they used to bypass the
    queue entirely (ROADMAP item). The "/cpupin" salt keeps the
    fingerprints disjoint from accelerator compiles of the same jaxpr.
    Any AOT-machinery failure falls back to the plain pinned jit; trace
    errors (NotCompilable) propagate either way."""

    def __init__(self, fn, tag: str = "", n_ops: int = 0,
                 deadline: float = 0.0):
        import jax

        self._raw = fn
        self._tag = tag
        self._n_ops = n_ops
        self._deadline = deadline or 0.0
        self._fn = jax.jit(fn)
        self._by_spec: dict = {}

    def _queue_entry(self, args):
        """(compiled-or-None, spec key) via the compile queue; None routes
        the call to the plain pinned jit. Must run inside the cpu pin.
        With a deadline set, CompileTimeout PROPAGATES — the host-CPU
        compile is itself killable (the flights wedge IS an XLA:CPU
        compile), and swallowing it into the unbounded plain jit would
        reintroduce the exact hang the deadline exists to kill."""
        from . import compilequeue as CQ

        try:
            avals, key = CQ._args_avals(args)
        except Exception:
            return None, None
        if avals is None:
            return None, None
        if key in self._by_spec:
            return self._by_spec[key], key
        try:
            entry = CQ.compile_traced(self._raw, avals, salt="/cpupin",
                                      tag=self._tag, n_ops=self._n_ops,
                                      deadline_s=self._deadline)
        except CQ._AotUnsupported:
            entry = None
        except CQ.CompileHazard:
            # a static veto predicts the hang itself — the plain pinned
            # jit below is exactly the unbounded compile it forbids, so
            # it must propagate even with the deadline off
            raise
        except CQ.CompileTimeout:
            if self._deadline > 0:
                raise
            entry = None
        self._by_spec[key] = entry
        return entry, key

    def __call__(self, *args, **kwargs):
        import jax

        from ..ops.strings import mxu_gather_override

        # default_backend() still reports the accelerator inside this
        # context, so force the CPU kernel formulations for the trace
        with jax.default_device(_cpu_device()), mxu_gather_override(False):
            if not kwargs:
                entry, key = self._queue_entry(args)
                if entry is not None:
                    try:
                        return entry(*args)
                    except TypeError:
                        # call-convention mismatch (weak-type drift): pin
                        # this spec to the plain jit like AotJit does
                        self._by_spec[key] = None
                    except Exception as e:
                        from . import compilequeue as CQ

                        if not CQ.deserialize_defect(e):
                            raise
                        # unloadable serialized executable: recompile
                        # in-process via the plain pinned jit (AotJit's
                        # fallback, under the cpu pin); persist the
                        # verdict so cold runs skip the doomed load
                        CQ.note_deserialize_defect(entry)
                        self._by_spec[key] = None
            return self._fn(*args, **kwargs)


@dataclass
class ExceptionRecord:
    op_id: int
    exc_name: str
    row: Any
    trace: Any = None    # cleaned user-frame traceback (sampled rows only)

    def __repr__(self):
        return f"<{self.exc_name} at op#{self.op_id}: {self.row!r}>"


@dataclass
class _Launch:
    """What a launched dispatch leaves on the window for its collect: a
    dispatch returns at launch, and the wait for its outputs happens
    once, at the head of that partition's collect (`_await_dispatch`).
    The collect side fills in how the window stood as the wait began."""

    #: host clock as the launch began
    t: float
    #: first call of its input spec: the trace and the compile or AOT
    #: load lie after `t`
    cold: bool
    #: dispatches launched and not yet collected as the wait began, this
    #: one included
    in_flight: int = 1
    #: when the stage's previous dispatch was seen ready (the chip runs
    #: one at a time)
    after: float = 0.0
    #: when the wait saw this one ready
    ready_at: float = 0.0


def _with_sites(device_fn, raw_fn):
    """`device_fn`, the jitted form of `raw_fn`, keeping `raw_fn`'s compiled
    regex sites (filled as it is traced): a stage's records count
    `regex_fallback_rows` where they are."""
    device_fn.regex_sites = getattr(raw_fn, "regex_sites", ())
    return device_fn


class _DispatchFailed:
    """Sentinel riding the dispatch window when the device call itself
    raised synchronously (wedged runtime, lost mesh) — the collect side
    re-raises it into the same retry -> elastic -> interpreter ladder as
    async failures surfacing at device_get."""

    def __init__(self, err: Exception):
        self.err = err


class _CompileTimedOut:
    """Sentinel riding the dispatch window when the stage executable's
    compile blew the deadline (killed child / negative-cache skip). NOT a
    task failure: per-partition retries can't help — the collect side
    restarts the WHOLE stage on one degraded tier (_TierRestart) so rows
    are never split across compiled/interpreted tiers mid-stage (the
    flights divergence, ROADMAP item b)."""

    def __init__(self, err: Exception):
        self.err = err


class _TierRestart(Exception):
    """Control flow: re-run the current stage from its first partition on
    `tier` ('cpu' = host-pinned compile, 'interpreter'). Raised by the
    windowed executor's collect side on a _CompileTimedOut sentinel and
    caught by _execute_windowed's tier loop — never escapes the stage."""

    def __init__(self, tier: str, cause: Exception):
        super().__init__(tier)
        self.tier = tier
        self.cause = cause


@dataclass
class StageResult:
    partitions: list[C.Partition]
    exceptions: list[ExceptionRecord] = field(default_factory=list)
    metrics: dict = field(default_factory=dict)


class JitCache:
    """LRU cache of compiled stage executables (reference analog: ORCv2
    LLJIT symbol cache, core/include/llvm13/JITCompiler_llvm13.h:30-72).

    Traced-shape bookkeeping lives WITH the cache entry and is dropped on
    eviction — round 1 bolted it on externally, so a rebuilt evicted stage
    claimed first_call=False and turned a trace failure into a hard raise."""

    def __init__(self, capacity: int = 128):
        self._store: OrderedDict = OrderedDict()
        self._traced: dict = {}           # key -> set of batch specs
        self.capacity = capacity
        self.hits = 0
        self.misses = 0

    def get_or_build(self, key, builder):
        if key in self._store:
            self.hits += 1
            self._store.move_to_end(key)
            return self._store[key]
        self.misses += 1
        fn = builder()
        self._store[key] = fn
        self._traced.pop(key, None)       # fresh executable: nothing traced
        if len(self._store) > self.capacity:
            old_key, _ = self._store.popitem(last=False)
            self._traced.pop(old_key, None)
        return fn

    def was_traced(self, key, spec) -> bool:
        return spec in self._traced.get(key, ())

    def note_traced(self, key, spec) -> None:
        self._traced.setdefault(key, set()).add(spec)


class LocalBackend:
    # selection-vector compaction is correct only where the plain dispatch/
    # collect path consumes '#rowidx' outputs; the mesh backend shards
    # batches across devices and keeps full-length outputs instead
    supports_compaction = True
    supports_fused_fold = True
    # where `_jit_stage_fn` sends a batch (`resolve:general`'s `path`)
    dispatch_path = "device"
    # a small violation set may resolve on a host-CPU executable
    # (_general_case_pass); a backend whose rows are not all in this
    # process opts out
    host_resolve = True
    # a transform stage's collect can box the action's answer partition by
    # partition (`execute_any(box_output=True)`)
    supports_box_output = True

    def __init__(self, options):
        self.options = options
        self.jit_cache = JitCache(options.get_int("tuplex.tpu.jitCacheSize", 128))
        self.interpret_only = options.get_bool("tuplex.tpu.interpretOnly")
        self.bucket_mode = options.get_str("tuplex.tpu.padBucketing", "q8")
        self._not_compilable: set[str] = set()
        # stages whose sample-estimated compaction bucket overflowed: re-run
        # and remember to build without compaction from then on
        self._compaction_off: set[str] = set()
        # what the precompile driver walked in this process: (build-cache
        # key, batch spec) -> the next stage's predicted avals, None where
        # the chain stops (_speculate)
        self._speculated: dict = {}
        from ..runtime.spill import MemoryManager

        self.mm = MemoryManager(
            options.get_size("tuplex.executorMemory", 1 << 30),
            options.get_str("tuplex.scratchDir", "/tmp/tuplex_tpu"))
        # task-level fault tolerance record (reference analog: the Lambda
        # backend's failure_log, AWSLambdaBackend.cc:410-474)
        self.failure_log: list[dict] = []
        # live per-partition progress hook (set by the driver around
        # execute_any; feeds history 'progress' events)
        self.progress_cb = None

    def fn_cache_salt(self) -> str:
        return ""   # mesh backends salt per mesh epoch (multihost.py)

    def touch_partition(self, part) -> None:
        self.mm.touch(part)

    def _jit_stage_fn(self, raw_fn, packed: bool = True, tag: str = "",
                      n_ops: int = 0):
        """Compile a stage fn for dispatch (overridden by MultiHostBackend
        to row-shard over a mesh). Input buffers are donated off-CPU: the
        staged batch is dead once the kernel reads it (consumers re-stage
        from host leaves or a one-shot handoff view), so XLA may reuse its
        HBM for the outputs (reference analog: partitions freed/recycled
        as tasks retire, Partition ref-counting).

        packed=False keeps per-leaf dict outputs — required where a
        consumer needs device-resident arrays (the intermediate-stage
        handoff, _attach_device_view).

        Per-spec compilation routes through exec/compilequeue: the
        content-addressed store dedups isomorphic stages in-process and
        reuses serialized executables across processes; `tag` attributes
        compile seconds to the owning stage (metrics 'compile_s') and
        `n_ops` is the stage's operator count for graphlint's vetting and
        the ``compile:*`` spans."""
        from ..runtime.jaxcfg import donation_enabled
        from ..runtime.packing import PackedStageFn, packing_enabled
        from .compilequeue import aot_jit

        donate = donation_enabled() and self.options.get_bool(
            "tuplex.tpu.donateBuffers", True)
        deadline = self.options.get_float("tuplex.tpu.compileDeadlineS", 0.0)
        if packed and type(self) is LocalBackend and packing_enabled():
            # single-buffer transfers both ways (see runtime/packing.py);
            # mesh backends keep per-leaf staging (sharded device_put)
            return PackedStageFn(raw_fn, donate, tag=tag, n_ops=n_ops,
                                 deadline=deadline)
        return aot_jit(raw_fn, donate=donate, salt=self.fn_cache_salt(),
                       tag=tag, n_ops=n_ops, deadline=deadline)

    # ------------------------------------------------------------------
    def _stage_build_args(self, stage, in_schema, intermediate):
        """(skey, use_comp, packed): which variant of `stage`'s fast-path
        fn a dispatch builds and keeps its bookkeeping under. One
        definition for `_run_stage_tier` and the precompile driver: a
        speculative compile is only worth its seconds where it is for the
        very function, under the very key, that dispatch looks up.
        `intermediate` is False or the consumer kind (execute_any)."""
        skey = stage.key() + "/" + (in_schema.name if in_schema else "") \
            + self.fn_cache_salt()
        use_comp = (self.supports_compaction
                    and self.options.get_bool(
                        "tuplex.tpu.filterCompaction", True)
                    and stage.key() not in self._compaction_off)
        # intermediate stages keep per-leaf dict outputs so the device-
        # resident handoff can gather from them; every other stage packs
        # its transfers into one buffer per direction. `intermediate` is
        # False or the consumer kind ("stage"/"join"/"agg" — round 5 only
        # plain stages qualified; joins and aggregates round-tripped every
        # boundary, VERDICT §2)
        packed = True
        if intermediate:
            from ..runtime.jaxcfg import device_handoff_enabled

            packed = not device_handoff_enabled(
                intermediate if isinstance(intermediate, str) else "stage")
        return skey, use_comp, packed

    def precompile_plan(self, stages, partitions, span=TR.NOOP):
        """Ahead-of-time compilation of the plan's LATER stages on the
        compile pool (exec/compilequeue), so stage i+1 compiles while
        stage i runs; returns the driver's Future, or None where nothing
        went to the pool. Speculative: stage avals are PREDICTED by
        chaining abstract shape evaluation from the source partitions in
        hand (one chain a distinct bucket: the short tail too), and
        dispatch verifies by content address, so a wrong prediction only
        wastes a background compile. Stage 0 is left to its own dispatch,
        which follows this call at once and loads or compiles the same
        executable itself. The reference compiles a stage in the
        milliseconds before its first task (LocalBackend.cc:865); an XLA
        compile is seconds to minutes, which is what the overlap is for.

        The driver ASKS before it traces (`_speculate`): a stage this
        backend already traced at those avals, or speculated earlier in
        this process, costs the pool nothing, so from its second job on a
        closed loop of one plan submits nothing. `span` (the caller's
        `compile:precompile-plan`) learns `submitted`, the chains handed
        to the pool, and `skipped`, the speculative submissions dropped
        on asking."""
        from . import compilequeue as CQ

        if type(self) is not LocalBackend:
            return None  # mesh/serverless dispatch builds other executables
        if self.interpret_only or not CQ.parallel_compile_enabled() \
                or not self.options.get_bool(
                    "tuplex.tpu.parallelCompile", True):
            return None
        planned = partitions.rows \
            if isinstance(partitions, C.PartitionStream) else partitions
        if not (isinstance(planned, list) and planned):
            return None
        try:
            batches = self._distinct_batches(partitions)
        except Exception:
            return None
        todo, skipped = [], 0
        for avals, schema in batches:
            _, n, answered = self._speculate(stages, avals, schema,
                                             own_dispatch=True,
                                             ask_only=True)
            if answered:
                skipped += n
            else:
                todo.append((avals, schema))
        CQ.note_prewarm_skipped(skipped)
        span.set("submitted", len(todo)).set("skipped", skipped)
        if not todo:
            return None
        # the pool hands the submitting span over (TR.handoff/adopt): the
        # driver's compiles name `compile:precompile-plan` and its job
        return CQ.pool().submit(self._precompile_driver, list(stages),
                                todo, True)

    def _distinct_batches(self, partitions) -> list:
        """(avals, schema) of each distinct dispatch batch among
        `partitions`, in order: the full bucket and the short tail's. A
        `PartitionStream` answers from its planned shapes, nothing built."""
        from ..compiler import stagefn as SF

        if isinstance(partitions, C.PartitionStream):
            shapes = [(partitions.template, m) for m in partitions.rows]
        else:
            shapes = [(part, part.num_rows) for part in partitions]
        seen: dict = {}
        for part, rows in shapes:
            avals = SF.partition_avals(part, self.bucket_mode, rows=rows)
            if avals is not None:
                seen.setdefault(_avals_spec(avals), (avals, part.schema))
        return list(seen.values())

    def _precompile_driver(self, stages, batches, own_dispatch=False):
        """The pool's half of `precompile_plan`: walk the plan from each
        of `batches` ((avals, schema) pairs; tests hand in partitions, or
        one), tracing where it must, and submit pool compiles. Returns
        the submitted futures (tests drive this synchronously, stage 0
        included)."""
        from . import compilequeue as CQ

        if not isinstance(batches, list):
            batches = [batches]
        if batches and not isinstance(batches[0], tuple):
            try:
                batches = self._distinct_batches(batches)
            except Exception:
                return []
        futs: list = []
        for avals, schema in batches:
            f, skipped, _ = self._speculate(stages, avals, schema,
                                            own_dispatch=own_dispatch)
            CQ.note_prewarm_skipped(skipped)
            futs.extend(f)
        return futs

    def _precompile_avals(self, stages, avals, schema):
        """`_precompile_driver` without a live partition: the
        respecialization controller (serve/respec) stores each tenant's
        stage-0 dispatch avals and replays them here — inside a
        compilequeue ``background_lane()`` — to compile a candidate stage
        set ahead of its canary with zero foreground partitions in hand
        (so stage 0 is speculated too: no dispatch is about to)."""
        return self._precompile_driver(stages, [(avals, schema)])

    def _speculate(self, stages, avals, schema, own_dispatch=False,
                   ask_only=False):
        """Walk the plan from one stage-0 batch (`avals`), predicting each
        stage's dispatch avals, and submit a pool compile for every stage
        nobody holds yet. Returns (futures, skipped, answered).

        Asked first, for each stage: did this backend's dispatch already
        trace that function at those avals (`jit_cache.was_traced`), or
        did an earlier walk of this backend speculate it
        (`_speculated`, which also remembers the next stage's avals)? Then
        nothing is built, traced or queued for it: `skipped` counts it,
        as it counts stage 0 under `own_dispatch` (its dispatch follows
        at once, so it is walked for its output avals only).
        `ask_only` stops at the first stage that would need a trace
        (`answered` False: the walk belongs on the pool); otherwise the
        stage is built with the arguments dispatch builds it with
        (`_stage_build_args`, `_jit_stage_fn`) and its own `warm` queues
        the compile.

        Prediction stops where shapes become data-dependent: pipeline
        breakers, filters/limits (output row count), compacted outputs,
        host-repacked wire layouts."""
        from ..compiler import stagefn as SF
        from ..plan import logical as L
        from ..plan.physical import TransformStage, consumer_kind
        from ..runtime.jaxcfg import jax

        futs: list = []
        skipped = 0
        for si, stage in enumerate(stages):
            if avals is None or not isinstance(stage, TransformStage) \
                    or stage.force_interpret:
                break
            skey, use_comp, packed = self._stage_build_args(
                stage, schema, consumer_kind(stages, si))
            if skey in self._not_compilable:
                break
            nxt = stages[si + 1] if si + 1 < len(stages) else None
            # whether the next stage's avals follow from this one's
            chains_on = isinstance(nxt, TransformStage) \
                and stage.limit < 0 and not any(
                    isinstance(op, L.FilterOperator) for op in stage.ops)
            cache_key = ("stagefn", skey, use_comp, packed)
            memo_key = (cache_key, _avals_spec(avals))
            known = memo_key in self._speculated
            # nothing to submit: stage 0's own dispatch is about to load or
            # compile this, or dispatch traced it, or a walk speculated it
            drop = (own_dispatch and si == 0) or known \
                or self.jit_cache.was_traced(*memo_key)
            skipped += drop
            if known:
                nxt_avals = self._speculated[memo_key]
            elif drop and not chains_on:
                break                   # and nothing to learn by tracing it
            elif ask_only:
                return futs, skipped, False
            else:
                try:
                    raw = stage.build_device_fn(
                        schema, compaction=use_comp,
                        fused_fold=self.supports_fused_fold)
                    out = jax.eval_shape(raw, avals)
                except Exception:
                    break
                if not drop:
                    try:
                        warm = getattr(self._jit_stage_fn(
                            raw, packed=packed, tag=stage.key(),
                            n_ops=len(stage.ops)), "warm", None)
                        f = warm(avals) if warm is not None else None
                        if f is not None:
                            futs.append(f)
                    except Exception:   # prewarm is speculative by contract
                        pass
                nxt_avals = SF.restage_avals(out, self.bucket_mode) \
                    if chains_on else None
                self._speculated[memo_key] = nxt_avals
            if not chains_on:
                break
            avals, schema = nxt_avals, nxt.input_schema
        return futs, skipped, True

    # ------------------------------------------------------------------
    def execute_any(self, stage, partitions, context,
                    intermediate=False, box_output=False) -> StageResult:
        """Dispatch by stage kind (reference: LocalBackend.cc:145-180).
        `intermediate`: a later stage consumes this one's output (enables
        the device-resident handoff; terminal outputs only ever go to
        host). It is False or the CONSUMER KIND — "stage" / "join" /
        "agg" — so the handoff gate can be tuned per consumer
        (jaxcfg.device_handoff_enabled). `box_output`: the action boxes
        this (last) stage's output; a transform stage boxes each partition
        at its collect, other kinds leave it to the action.

        Transfer attribution happens HERE, for every stage kind: the
        stage's xferstats delta (d2h/h2d bytes) lands on its metrics
        record, so join/aggregate transfers count the same as transform
        stages and `Metrics.d2hBytes()` agrees with the counter registry
        for work done inside stages."""
        from ..plan.physical import AggregateStage, JoinStage

        x_snap = xferstats.snapshot()
        if isinstance(stage, AggregateStage):
            from .aggexec import AggregateExecutor

            res = AggregateExecutor(self).execute(stage, partitions or [])
        elif isinstance(stage, JoinStage):
            from .joinexec import JoinExecutor

            res = JoinExecutor(self).execute(stage, partitions or [],
                                             context,
                                             intermediate=intermediate)
        else:
            res = self.execute(stage, partitions or [],
                               intermediate=intermediate,
                               box_output=box_output)
        xd = xferstats.delta(x_snap)
        res.metrics["d2h_bytes"] = xd["d2h_bytes"]
        res.metrics["h2d_bytes"] = xd["h2d_bytes"]
        return res

    # ------------------------------------------------------------------
    def execute(self, stage: TransformStage, partitions,
                intermediate: bool = False,
                box_output: bool = False) -> StageResult:
        """Span-wrapped stage entry: one `stage:execute` span per stage
        (runtime/tracing); transfer attribution happens in execute_any so
        every stage kind gets it; the windowed impl below does the
        dual-mode work."""
        with TR.span("stage:execute", "exec") as sp:
            if sp is not TR.NOOP:
                sp.set("kind", type(stage).__name__)
                sp.set("key", stage.key()[:12]).set("n_ops", len(stage.ops))
            res = self._execute_windowed(stage, partitions, intermediate,
                                         box_output)
            if sp is not TR.NOOP:
                sp.set("rows_out", res.metrics.get("rows_out", 0))
                for k in ("device_s", "flops", "hbm_peak",
                          "roofline_frac"):
                    v = res.metrics.get(k)
                    if v is not None:
                        sp.set(k, round(float(v), 6))
        return res

    def _execute_windowed(self, stage: TransformStage,
                          partitions,
                          intermediate: bool = False,
                          box_output: bool = False) -> StageResult:
        """Window-pipelined dual-mode execution (reference analog:
        Executor/WorkQueue task parallelism, Executor.h:45-109 +
        LocalBackend.cc:1531-1586). Device dispatch is ASYNC — while the
        device crunches partition i, the host stages partition i+1 and
        merges partition i-1; `partitions` may be a lazy iterator, so
        take(n) stops pulling source data once the limit is satisfied.

        This wrapper is the TIER loop: a stage whose executable compile
        blows the deadline (killed compile child, `.timeout` negative
        cache) is restarted FROM ITS FIRST PARTITION on one degraded
        tier — host-CPU compile where that's a distinct backend, else
        interpreter — because results already emitted on the compiled
        tier must not be merged with later rows from a different tier
        (the mixed compiled/interpreted divergence observed on flights,
        ROADMAP item b). Every pulled partition is recorded so the
        replay sees exactly the same input; the few duplicated dispatch
        seconds are the price of tier purity."""
        from itertools import chain

        parts_it = iter(partitions)
        first_part = next(parts_it, None)

        def parts_stream():
            if first_part is not None:
                yield first_part
            yield from parts_it

        prefetch = max(0, self.options.get_int(
            "tuplex.tpu.sourcePrefetch", 2))
        live = _prefetch_iter(parts_stream(), prefetch) if prefetch \
            else parts_stream()
        seen: list = []
        # replay retention costs O(input) partition references (spilled,
        # not resident, under memory pressure — but still disk): only pay
        # it where a CompileTimeout can actually happen. With the
        # deadline disabled (or interpret-only) the restart is
        # unreachable and streaming retention stays O(window).
        record_replay = not self.interpret_only and self.options.get_float(
            "tuplex.tpu.compileDeadlineS", 0.0) > 0

        def recording():
            for p in live:
                if record_replay:
                    seen.append(p)
                yield p

        rec = recording()
        tier = "device"
        restarts = 0
        while True:
            stream = chain(list(seen), rec) if restarts else rec
            try:
                res = self._run_stage_tier(stage, stream, first_part,
                                           intermediate, tier, box_output)
                res.metrics["tier_restarts"] = restarts
                return res
            except _TierRestart as tr:
                restarts += 1
                # the re-run re-records every partition the aborted tier
                # already processed: back out this execution's exception-
                # plane accounting so rows_seen/exception_rate and the
                # drift windows don't double-count (BEFORE any overlay
                # revert below — the discard must hit the key the aborted
                # execution recorded under)
                if EX.enabled():
                    EX.discard_stage(stage.key(), owner=id(self))
                from ..utils.logging import get_logger

                # re-specialization fallback rung (serve/respec): a stage
                # running under a promoted candidate overlay whose
                # compile blows the deadline falls back onto the RETAINED
                # INCUMBENT configuration first — same 'device' tier,
                # previous plan generation, restarted from partition 0 so
                # rows are never split across plan generations mid-stage
                # (the PR-8 tier-purity invariant, extended to
                # generations). The controller is told so it quarantines
                # the candidate and demotes the tenant for future jobs.
                rev = getattr(stage, "_respec_revert", None)
                if rev is not None:
                    for k, v in rev.items():
                        setattr(stage, k, v)
                    stage._respec_revert = None
                    for memo in ("_resolve_plan_memo",):
                        if hasattr(stage, memo):
                            try:
                                delattr(stage, memo)
                            except AttributeError:
                                pass
                    notify = getattr(stage, "_respec_notify", None)
                    if notify is not None:
                        try:
                            notify(tr.cause)
                        except Exception:   # controller is advisory here
                            pass
                    tier = "device"
                    get_logger("exec").warning(
                        "stage %s failed under its re-specialized "
                        "generation (%s); restarting the whole stage on "
                        "the retained incumbent (restart %d)",
                        stage.key()[:12], tr.cause, restarts)
                    continue
                # a degraded tier timing out again steps down once more;
                # the cap is belt-and-braces (the ladder is 3 rungs)
                tier = "interpreter" if restarts >= 3 else tr.tier
                get_logger("exec").warning(
                    "stage %s compile deadline (%s); restarting the "
                    "whole stage on the %s tier (restart %d)",
                    stage.key()[:12], tr.cause, tier, restarts)

    def _run_stage_tier(self, stage: TransformStage, stream, first_part,
                        intermediate, tier: str,
                        box_output: bool = False) -> StageResult:
        """One tier attempt of the windowed executor. `tier` is 'device'
        (normal: accelerator/packed compile), 'cpu' (host-pinned compile
        after a device-tier deadline) or 'interpreter' (no compiled fast
        path at all). Raises _TierRestart when a compile deadline means
        the stage must re-run one rung down. `box_output`: each output
        partition is boxed (`Partition.boxed`) as it is collected, while
        the chip runs the dispatches behind it; rows boxed on an aborted
        tier go with its `out_parts`."""
        from collections import deque

        from . import compilequeue as CQ

        t0 = time.perf_counter()
        mm_snap = self.mm.metrics_snapshot()
        fl_snap = len(self.failure_log)
        metrics: dict[str, Any] = {"fast_path_s": 0.0, "slow_path_s": 0.0,
                                   "general_path_s": 0.0, "compile_s": 0.0,
                                   # partitions run again without compaction
                                   # after their bucket overflowed
                                   "compaction_reruns": 0,
                                   # which side set the stage's pace: the
                                   # collects whose wait found the chip
                                   # still busy, and those whose outputs
                                   # were ready when the host came
                                   "dispatches_waited": 0,
                                   "dispatches_ready": 0,
                                   # output partitions boxed at their
                                   # collect, inside the window
                                   "partitions_boxed": 0}
        if EX.enabled():
            # exception-plane baseline (runtime/excprof): snapshot the
            # plan-time code inventory + resolve-plan verdict BEFORE any
            # row executes — the drift detector compares live windows
            # against exactly this expectation
            EX.capture_baseline(stage)
        device_fn = None
        in_schema = first_part.schema if first_part is not None else None
        skey, use_comp, packed = self._stage_build_args(stage, in_schema,
                                                        intermediate)
        consumer = intermediate if isinstance(intermediate, str) else "stage"
        if tier != "interpreter" and not self.interpret_only \
                and skey not in self._not_compilable \
                and in_schema is not None:
            device_fn, use_comp = self._build_stage_fn(
                stage, in_schema, skey, use_comp, packed=packed,
                force_cpu=(tier == "cpu"))

        out_parts: list[C.Partition] = []
        exceptions: list[ExceptionRecord] = []
        emitted_total = 0
        if intermediate:
            from ..runtime.jaxcfg import (device_handoff_budget_bytes,
                                          device_handoff_enabled)

            # fold enablement into the flag once per stage (not per
            # partition) and probe the HBM budget only when it matters
            intermediate = device_handoff_enabled(consumer)
            self._handoff_left = \
                device_handoff_budget_bytes() if intermediate else 0
        limit = stage.limit
        window_size = max(1, self.options.get_int(
            "tuplex.tpu.dispatchWindow", 3))
        window: deque = deque()
        last_ready = 0.0    # when the stage's last dispatch was seen ready

        from ..utils.signals import check_interrupted

        def armed(launch):
            """How the window stands as this dispatch's wait begins."""
            if launch is not None:
                launch.in_flight = 1 + sum(
                    1 for e in window if e[3] is not None)
                launch.after = last_ready
            return launch

        def collect(part, outs, dispatch_s, launch):
            nonlocal last_ready
            try:
                return self._collect_partition(
                    stage, part, outs, dispatch_s,
                    intermediate=intermediate, launch=armed(launch),
                    regex_sites=getattr(device_fn, "regex_sites", ()))
            finally:
                if launch is not None and launch.ready_at:
                    last_ready = launch.ready_at

        def collect_one():
            nonlocal emitted_total, device_fn, use_comp, skey
            part, outs, dispatch_s, launch = window.popleft()
            if limit >= 0 and emitted_total >= limit:
                return  # limit met: drop already-dispatched work unprocessed
            if isinstance(outs, _DispatchFailed) \
                    and isinstance(outs.err, CQ.CompileTimeout):
                outs = _CompileTimedOut(outs.err)
            if isinstance(outs, _CompileTimedOut):
                # a blown compile deadline is NOT a task failure: retrying
                # the partition would re-burn the deadline and a per-
                # partition interpreter fallback would split the stage's
                # rows across tiers — restart the whole stage one rung down
                raise _TierRestart(self._next_tier(tier), outs.err)
            # registering a previous output may have spilled this partition
            # in the dispatch->collect gap; touch swaps it back in and the
            # pin keeps it resident against concurrent prefetch mm calls
            self.mm.pin(part)
            try:
                try:
                    if isinstance(outs, _DispatchFailed):
                        raise outs.err
                    outp, excs, m = collect(part, outs, dispatch_s, launch)
                except Exception as e:
                    if outs is None:
                        raise   # interpreter failure is deterministic
                    # device-task failure: retry the dispatch once, then run
                    # the partition entirely on the interpreter — a failing
                    # DEVICE task degrades, never kills the job (reference:
                    # failure_log, AWSLambdaBackend.cc:410-474)
                    from ..utils.logging import get_logger

                    if CQ.deserialize_defect(e):
                        # the loads-but-cannot-run gap surfaced at the
                        # COLLECT site (a dispatch returns at launch:
                        # what fails once the chip runs it fails at this
                        # partition's wait or fetch).
                        # Pin the doomed specs + persist their .nodeser
                        # markers now so the retry below re-dispatches on
                        # a fresh in-process compile instead of the same
                        # defective executable
                        noted = getattr(device_fn, "note_async_defect",
                                        None)
                        if noted is not None and noted():
                            get_logger("exec").warning(
                                "deserialized executable failed at "
                                "collect (%s); recompiling in-process "
                                "before the retry", str(e)[:200])
                    self.failure_log.append({
                        "stage": skey[:16], "start_index": part.start_index,
                        "rows": part.num_rows, "attempt": 1,
                        "error": f"{type(e).__name__}: {e}",
                        "action": "retry"})
                    get_logger("exec").warning(
                        "partition task failed (%s: %s); retrying once",
                        type(e).__name__, e)
                    try:
                        outp, excs, m = collect(*self._dispatch_partition(
                            part, device_fn, skey, use_comp, stage,
                            packed=packed))
                    except Exception as e2:
                        efn = self._elastic_stage_fn(stage, skey, in_schema)
                        outp = None
                        if efn is not None:
                            # elastic tier: the distributed dispatch is
                            # broken (lost device / wedged collective) —
                            # degrade to a non-mesh COMPILED fn for this
                            # and all later partitions of the stage
                            # (reference analog: Lambda re-invokes failed
                            # tasks on fresh workers)
                            self.failure_log.append({
                                "stage": skey[:16],
                                "start_index": part.start_index,
                                "rows": part.num_rows, "attempt": 2,
                                "error": f"{type(e2).__name__}: {e2}",
                                "action": "elastic"})
                            ekey = skey + "/elastic"
                            try:
                                res3 = self._dispatch_partition(
                                    part, efn, ekey, False, stage,
                                    packed=packed)
                                if res3[1] is None:
                                    # elastic fn couldn't trace either:
                                    # demote the whole stage cleanly
                                    self._not_compilable.add(skey)
                                else:
                                    outp, excs, m = collect(*res3)
                                    # later partitions ride the elastic fn
                                    # UNDER ITS OWN bookkeeping key (the
                                    # mesh fn's traced-spec records must
                                    # not vouch for a different fn)
                                    device_fn, use_comp = efn, False
                                    skey = ekey
                                    # report the rung that actually fired
                                    # (the reduced-mesh tier logs an
                                    # 'elastic-mesh' entry; otherwise it
                                    # was the single-device fallback)
                                    rung = ("reduced-mesh execution"
                                            if any(r.get("action") ==
                                                   "elastic-mesh"
                                                   for r in
                                                   self.failure_log[-2:])
                                            else "single-device execution")
                                    get_logger("exec").warning(
                                        "mesh dispatch failed twice "
                                        "(%s: %s); stage degraded to %s",
                                        type(e2).__name__, e2, rung)
                            except Exception as e3:
                                self.failure_log.append({
                                    "stage": skey[:16],
                                    "start_index": part.start_index,
                                    "rows": part.num_rows, "attempt": 3,
                                    "error":
                                        f"{type(e3).__name__}: {e3}",
                                    "action": "elastic-failed"})
                                outp = None
                        if outp is None:
                            self.failure_log.append({
                                "stage": skey[:16],
                                "start_index": part.start_index,
                                "rows": part.num_rows, "attempt": 2,
                                "error": f"{type(e2).__name__}: {e2}",
                                "action": "interpreter"})
                            get_logger("exec").warning(
                                "retry failed (%s: %s); partition runs on "
                                "the interpreter", type(e2).__name__, e2)
                            outp, excs, m = collect(part, None, 0.0, None)
            finally:
                self.mm.unpin(part)
            metrics["fast_path_s"] += m.get("fast_path_s", 0.0)
            metrics["slow_path_s"] += m.get("slow_path_s", 0.0)
            metrics["general_path_s"] += m.get("general_path_s", 0.0)
            metrics["compaction_reruns"] += m.get("compaction_reruns", 0)
            if "regex_fallback_rows" in m:
                # rows a compiled regex site could not decide
                metrics["regex_fallback_rows"] = metrics.get(
                    "regex_fallback_rows", 0) + m["regex_fallback_rows"]
            metrics["dispatches_waited"] += m.get("dispatches_waited", 0)
            metrics["dispatches_ready"] += m.get("dispatches_ready", 0)
            exceptions.extend(excs)
            if limit >= 0 and emitted_total + outp.num_rows > limit:
                outp = _truncate_partition(outp, limit - emitted_total)
            if box_output:
                # before it is registered: the memory manager must not
                # spill a partition only to read it back for its rows
                outp.boxed = C.box_partition(outp, self.touch_partition,
                                             in_window=True)
                metrics["partitions_boxed"] += 1
            self.mm.register(outp)
            emitted_total += outp.num_rows
            out_parts.append(outp)
            if self.progress_cb is not None:
                try:    # live history event (webui liveness, VERDICT r3 #9)
                    self.progress_cb(len(out_parts), emitted_total)
                except Exception:
                    pass

        for part in stream:
            check_interrupted()
            if limit >= 0 and emitted_total >= limit:
                break
            if skey in self._not_compilable or tier == "interpreter":
                device_fn = None
            elif use_comp and stage.key() in self._compaction_off:
                # an earlier partition overflowed (or failed to trace) under
                # compaction: rebuild the plain fn instead of paying the
                # dispatch-then-redo cost for every remaining partition
                device_fn, use_comp = self._build_stage_fn(
                    stage, in_schema, skey, False, packed=packed,
                    force_cpu=(tier == "cpu"))
            self.mm.touch(part)
            try:
                window.append(self._dispatch_partition(part, device_fn,
                                                       skey, use_comp,
                                                       stage,
                                                       packed=packed))
            except Exception as e:
                # synchronous dispatch failure: enqueue for the collect
                # side's degrade ladder instead of killing the job
                window.append((part, _DispatchFailed(e), 0.0, None))
            if len(window) >= window_size:
                collect_one()
        while window:
            check_interrupted()
            collect_one()

        # per-stage compile seconds (JobMetrics.h discipline): whatever the
        # compile queue spent building THIS stage's executables — whether
        # inline at first dispatch or ahead-of-time on the pool — lands on
        # this stage's record; AOT/dedup hits cost 0 here by construction
        from . import compilequeue as _cq

        cs, cn = _cq.consume_tag(stage.key())
        metrics["compile_s"] += cs
        metrics["stage_compiles"] = cn
        # static-vetting attribution (compiler/graphlint): lint cost and
        # hazard verdicts for THIS stage — submission-time vetoes via the
        # queue's per-tag ledger, plan-time pre-degrades via the report
        # the planner left on the stage itself
        gl_ms, gl_found, gl_avoided = _cq.consume_graphlint(stage.key())
        rep = getattr(stage, "graph_report", None)
        if rep is not None:
            gl_ms += rep.elapsed_ms
        if getattr(stage, "hazard_rule", None):
            gl_found += 1
            gl_avoided += 1
            metrics["hazard_rule"] = stage.hazard_rule
        if gl_ms or gl_found:
            metrics["graphlint_ms"] = round(gl_ms, 3)
            metrics["hazards_found"] = gl_found
            metrics["hazards_avoided"] = gl_avoided
        # device-plane cost attribution (runtime/devprof): measured device
        # seconds, XLA flops/bytes/peak-memory and the roofline fraction
        # for THIS stage's dispatches, flat numeric keys riding the same
        # record compile_s does (bench JSON, history, Prometheus)
        try:
            # owner = this backend: concurrent serve jobs share stage
            # keys by design (isomorphic compile sharing) but must not
            # pool or steal each other's dispatch windows
            rep = DP.stage_report(stage.key(), mm_budget=self.mm.budget,
                                  owner=id(self))
            if rep:
                metrics.update(rep)
        except Exception:   # pragma: no cover - attribution best-effort
            pass
        # exception-plane accounting (runtime/excprof): rows seen, the
        # exception rate, unexpected-code rows and the per-tier retired
        # counts — flat numeric keys riding the same stage record
        try:
            exrep = EX.stage_report(stage.key(), owner=id(self))
            if exrep:
                metrics.update(exrep)
        except Exception:   # pragma: no cover - attribution best-effort
            pass
        # which tier this stage's rows ran on (tier purity is the
        # contract the deadline-degrade restart enforces); task-failure
        # fallbacks within the ladder still show up in failure_log. A
        # stage whose fast path never built or traced ran its partitions
        # in the interpreter whatever rung it was started on.
        if self.interpret_only or skey in self._not_compilable:
            tier = "interpreter"
        metrics["tier"] = {"device": "compiled", "cpu": "cpu-compiled",
                           "interpreter": "interpreter"}[tier]
        metrics["wall_s"] = time.perf_counter() - t0
        metrics["rows_out"] = emitted_total
        metrics["exception_rows"] = len(exceptions)
        # one failed task may log retry AND degrade entries: count tasks
        metrics["task_failures"] = sum(
            1 for e in self.failure_log[fl_snap:] if e.get("attempt") == 1)
        metrics.update(self.mm.metrics_delta(mm_snap))
        return StageResult(out_parts, exceptions, metrics)

    # ------------------------------------------------------------------
    def _attach_device_view(self, outp: C.Partition, pending_outs) -> None:
        """Keep a device-resident gathered view of this output partition so
        a downstream stage re-stages it without host copies + H2D (reference
        analog: hash intermediates passed by pointer as stage globals,
        LocalBackend.cc:903-908 — here the 'pointer' is a device buffer).
        Best-effort: any mismatch falls back to host staging."""
        try:
            from ..runtime.jaxcfg import jnp

            if not isinstance(pending_outs, dict):
                return   # packed results skip the device view (terminal path)
            expect = C.staged_keys(outp)
            if expect is None or not expect <= set(pending_outs):
                return
            m = outp.num_rows
            if m == 0:
                return
            b2 = C.bucket_size(m, self.bucket_mode)
            # charge the per-stage HBM budget BEFORE building the view: a
            # stage's whole output holds views until the next stage drains
            # them, so unbounded attachment would pin O(dataset) HBM
            est = b2 + sum(
                (pending_outs[k].nbytes // max(1, pending_outs[k].shape[0]))
                * b2 for k in expect)
            if est > getattr(self, "_handoff_left", 0):
                return
            self._handoff_left -= est
            src = np.zeros(b2, dtype=np.int32)
            src[:m] = outp._gather_src
            with TR.span("handoff:view", "xfer") as sp:
                idx = jnp.asarray(src)
                arrays = {k: take_view(pending_outs[k], idx)
                          for k in expect}
                _note_view(sp, arrays)
            rv = np.zeros(b2, dtype=np.bool_)
            rv[:m] = True
            arrays["#rowvalid"] = jnp.asarray(rv)
            arrays["#seed"] = C.partition_seed(outp)
            outp.device_batch = C.DeviceBatch(
                arrays=arrays, n=m, b=b2, schema=outp.schema)
        except Exception:   # pragma: no cover - purely an optimization
            outp.device_batch = None

    # ------------------------------------------------------------------
    def _lazy_merge(self, stage, part: C.Partition,
                    compiled_ok: np.ndarray, data_arrays: dict,
                    src_map: Optional[np.ndarray]) -> Optional[C.Partition]:
        """Fast-path merge that NEVER fetches the data columns: the output
        partition's host leaves are lazy (device-backed, materialized
        per-leaf only if some consumer needs host bytes) and a gathered
        device view feeds the next stage directly. Returns None when the
        layout can't go device-resident — the caller then runs the normal
        host merge. Best-effort by design: host semantics are identical
        either way."""
        try:
            import jax

            from ..plan.physical import runtime_output_columns
            from ..runtime import xferstats
            from ..runtime.jaxcfg import jnp

            if not data_arrays:
                return None
            comp_src = np.nonzero(compiled_ok)[0].astype(np.int64)
            m = int(comp_src.size)
            if src_map is not None:
                comp_src = src_map[comp_src]
            n_full = int(next(iter(data_arrays.values())).shape[0])
            if comp_src.size and int(comp_src.max()) >= n_full:
                return None
            # schema straight off the device arrays' keys/dtypes (no
            # transfer — type_from_result_arrays reads .dtype only)
            col_types = []
            while True:
                t = C.type_from_result_arrays(data_arrays,
                                              str(len(col_types)))
                if t is None:
                    break
                col_types.append(t)
            if not col_types:
                return None
            out_cols = runtime_output_columns(part.schema, stage.ops)
            names = tuple(out_cols) if out_cols \
                and len(out_cols) == len(col_types) \
                else tuple(f"_{i}" for i in range(len(col_types)))
            schema = T.row_of(names, col_types)
            leaf_types: dict[str, T.Type] = {}
            for ci, ct in enumerate(col_types):
                for pth, lt in C.flatten_type(ct, str(ci)):
                    leaf_types[pth] = lt
            expect: set = set()
            for pth in leaf_types:
                expect.update(C.result_keys_for_leaf(data_arrays, pth))
            if expect != set(data_arrays):
                return None      # keys the consumer wouldn't re-stage
            if m == 0:
                # fully-filtered partition: synthesize the empty output
                # straight from the arrays' dtypes — zero data bytes
                # cross the wire for a 0-row result
                arrs = {k: np.zeros((0,) + tuple(v.shape[1:]),
                                    np.dtype(v.dtype))
                        for k, v in data_arrays.items()}
                leaves = {pth: C.leaf_from_result_arrays(arrs, pth, lt, 0)
                          for pth, lt in leaf_types.items()}
                outp = C.Partition(schema=schema, num_rows=0,
                                   leaves=leaves,
                                   start_index=part.start_index)
                outp._gather_src = comp_src
                return outp
            # HBM budget: the raw outputs stay pinned until the lazy
            # leaves are dropped/forced, and the gathered view rides on
            # top — charge both against the per-stage cap
            b2 = C.bucket_size(m, self.bucket_mode)
            est = b2 + sum(
                (v.nbytes // max(1, int(v.shape[0]))) * b2
                for v in data_arrays.values())
            if est * 2 > getattr(self, "_handoff_left", 0):
                return None
            self._handoff_left -= est * 2

            src = np.zeros(b2, dtype=np.int32)
            src[:m] = comp_src
            with TR.span("handoff:view", "xfer") as sp:
                idx = jnp.asarray(src)
                view = {k: take_lazy(data_arrays[k], idx) for k in expect}
                _note_view(sp, view)
            rv = np.zeros(b2, dtype=np.bool_)
            rv[:m] = True
            view["#rowvalid"] = jnp.asarray(rv)

            outp = C.Partition(schema=schema, num_rows=m, leaves={},
                               start_index=part.start_index)
            outp._gather_src = comp_src
            view["#seed"] = C.partition_seed(outp)
            gsrc = jnp.asarray(comp_src)

            def loader(pth):
                arrs = {}
                for k in C.result_keys_for_leaf(data_arrays, pth):
                    g = take_load(data_arrays[k], gsrc)
                    h = np.asarray(jax.device_get(g))
                    xferstats.note_d2h(h.nbytes, tag="lazy_load")
                    arrs[k] = h
                return C.leaf_from_result_arrays(arrs, pth,
                                                 leaf_types[pth], m)

            ll = C.LazyLeaves(leaf_types.keys(), loader, tag="stage")
            ll.nbytes_hint = est
            outp.leaves = ll
            outp.device_batch = C.DeviceBatch(arrays=view, n=m, b=b2,
                                              schema=schema)
            return outp
        except Exception:   # pragma: no cover - purely an optimization
            return None

    # ------------------------------------------------------------------
    def _elastic_stage_fn(self, stage, skey: str, in_schema):
        """Compiled fallback when the PRIMARY dispatch path is broken, or
        None (single-device backends have nothing between retry and the
        interpreter; the mesh backend degrades to a non-mesh executable)."""
        return None

    # ------------------------------------------------------------------
    def _next_tier(self, tier: str) -> str:
        """One rung down the stage-tier ladder after a compile deadline:
        device-compiled -> host-CPU-compiled (only where the host CPU is
        a DISTINCT backend — on a CPU default backend the same XLA:CPU
        compile would wedge again) -> interpreter."""
        if tier == "device" and type(self) is LocalBackend \
                and _cpu_device() is not None:
            from ..runtime.jaxcfg import jax as _jax

            if _jax.default_backend() != "cpu":
                return "cpu"
        return "interpreter"

    # ------------------------------------------------------------------
    def _build_stage_fn(self, stage, in_schema, skey: str, use_comp: bool,
                        packed: bool = True, force_cpu: bool = False):
        """Build + jit the fast-path fn. A build failure under compaction
        retries without it (an opt-in optimization must never demote the
        stage to the interpreter); only a plain build failure does that.
        ``force_cpu`` is the deadline-degrade 'cpu' tier: pin the compile
        to the host CPU backend."""
        cpu_pin = force_cpu and _cpu_device() is not None
        if cpu_pin:
            from ..runtime.jaxcfg import jax as _jax

            cpu_pin = _jax.default_backend() != "cpu"
        while True:
            try:
                raw_fn = stage.build_device_fn(
                    in_schema, compaction=use_comp,
                    fused_fold=self.supports_fused_fold)
                if cpu_pin:
                    # the deadline-degrade 'cpu' tier: the stage compiles
                    # on the host CPU backend instead — device transfers
                    # still happen at the stage boundary, only the
                    # compute stays host-side. _CpuJit routes the compile
                    # through compilequeue.compile_traced (traced under
                    # the cpu pin), so it is counted into the stage's
                    # compile_s/stage_compiles, cached, reused — and
                    # still deadline-bounded (an XLA:CPU compile can
                    # wedge too; CompileTimeout propagates to the tier
                    # ladder's next rung).
                    deadline = self.options.get_float(
                        "tuplex.tpu.compileDeadlineS", 0.0)
                    return self.jit_cache.get_or_build(
                        ("stagefn", skey, use_comp, "cpupin"),
                        lambda: _with_sites(_CpuJit(
                            raw_fn, tag=stage.key(), n_ops=len(stage.ops),
                            deadline=deadline), raw_fn)), use_comp
                return self.jit_cache.get_or_build(
                    ("stagefn", skey, use_comp, packed),
                    lambda: _with_sites(self._jit_stage_fn(
                        raw_fn, packed=packed, tag=stage.key(),
                        n_ops=len(stage.ops)), raw_fn)), use_comp
            except NotCompilable:
                self._not_compilable.add(skey)
                return None, use_comp
            except Exception as e:
                from ..utils.logging import get_logger

                if use_comp:
                    get_logger("exec").warning(
                        "stage build failed under compaction (%s: %s); "
                        "retrying without", type(e).__name__, e)
                    self._compaction_off.add(stage.key())
                    use_comp = False
                    continue
                get_logger("exec").warning(
                    "stage build failed (%s: %s); falling back to the "
                    "interpreter", type(e).__name__, e)
                self._note_demotion(skey, "build", e)
                return None, use_comp

    # ------------------------------------------------------------------
    def _dispatch_partition(self, part: C.Partition, device_fn, skey: str,
                            use_comp: bool = False, stage=None,
                            packed: bool = True):
        """Stage the batch and launch the device call WITHOUT blocking:
        jax dispatch is async, nothing between the launch and the return
        polls, blocks or fetches (devprof on or off, traced or not), and
        the outputs are awaited once, at the head of this partition's
        collect (`_await_dispatch`), so the window's other dispatches run
        on the chip beside the host. Returns (part, pending_outs | None,
        dispatch_seconds, _Launch | None)."""
        if device_fn is None or part.n_normal() == 0:
            return (part, None, 0.0, None)
        faults.maybe("dispatch")   # chaos checkpoint (runtime/faults): a
        # raise here rides the window as _DispatchFailed into the same
        # retry -> degrade ladder a real device failure takes
        t0 = time.perf_counter()
        with TR.span("partition:dispatch", "exec") as _sp:
            _sp.set("rows", part.num_rows).set("start", part.start_index) \
                .set("compacted", int(use_comp))
            with TR.span("h2d:leaf-stage", "xfer") as _hsp:
                batch = C.stage_partition(part, self.bucket_mode)
                leaf_h2d = 0
                if not isinstance(device_fn, PackedStageFn):
                    # per-leaf staging: the jit call uploads the numpy
                    # arrays (packed dispatch notes its own single-buffer
                    # H2D; arrays already device-resident — the handoff
                    # view — cost 0). Counted AFTER the call succeeds — a
                    # first-call trace failure re-enters here via
                    # _redispatch_plain and would otherwise double-count
                    # an upload that never happened
                    leaf_h2d = C.host_nbytes(batch.arrays)
                _hsp.set("bytes", leaf_h2d)
            return self._dispatch_launch(part, device_fn, skey, use_comp,
                                         stage, packed, batch, t0,
                                         leaf_h2d=leaf_h2d)

    def _dispatch_launch(self, part, device_fn, skey, use_comp, stage,
                         packed, batch, t0, leaf_h2d: int = 0):
        # `packed` mirrors the build-cache key: a stage built in BOTH
        # variants (handoff toggled) must not let one variant's traced
        # specs vouch for the other — a first-call trace failure would
        # then raise instead of demoting to the interpreter (ADVICE r5)
        cache_key = ("stagefn", skey, use_comp, packed)
        spec = batch.spec()                     # jit retraces per shape
        first_call = not self.jit_cache.was_traced(cache_key, spec)
        # the launch stamp the collect side's wait measures from (devprof's
        # launch -> seen-ready sample): before the call, so a first call's
        # trace, compile or AOT load lies inside a cold sample
        launch = _Launch(time.perf_counter(), first_call)
        try:
            # name formatted only when tracing is on — dispatch is the
            # per-partition hot path and the off-path must stay free
            with TR.span("dispatch:launch", "exec") as _lsp:
                with TR.device_annotation(f"tpx:dispatch:{skey[:12]}"
                                          if TR.enabled() else ""):
                    outs = device_fn(batch.arrays)
                if _lsp is not TR.NOOP:
                    # the executable actually launched (de-duplication may
                    # hand this stage another stage's): joins the span to
                    # the profiler's `XLA Modules` line
                    _lsp.set("module",
                             getattr(device_fn, "last_module", None)) \
                        .set("first_call", int(first_call))
            if leaf_h2d:
                xferstats.note_h2d(leaf_h2d, tag="leaf_stage")
            self.jit_cache.note_traced(cache_key, spec)
        except NotCompilable:
            # surfaces at TRACE time (first call): drop compaction first if
            # it was on (it may be the culprit) and re-dispatch THIS
            # partition with the plain fn; only that failing too routes to
            # the interpreter
            if use_comp:
                return self._redispatch_plain(part, skey, stage, t0,
                                              packed=packed)
            self._not_compilable.add(skey)
            return (part, None, time.perf_counter() - t0, None)
        except CompileTimeout as e:
            # the executable's compile was killed at the deadline (or the
            # `.timeout` negative cache skipped it): NOT a per-partition
            # problem — ride the window as a sentinel so the collect side
            # restarts the WHOLE stage on one degraded tier
            return (part, _CompileTimedOut(e), time.perf_counter() - t0,
                    None)
        except Exception as e:
            if not first_call:
                raise  # executed before: a real runtime failure
            from ..utils.logging import get_logger

            from . import compilequeue as CQ

            if CQ.deserialize_defect(e):
                # the fork-handback executable LOADED but failed as the
                # first call ran it, OUTSIDE AotJit.__call__'s defect
                # handler (what surfaces only once the chip gets to it
                # takes the same route from the collect side's wait:
                # `collect_one`). Pin the doomed specs to the plain
                # in-process jit (persisting their `.nodeser` markers
                # for cold runs) and retry this partition once on the
                # recompiled path instead of demoting the stage to the
                # interpreter. A second failure finds nothing left to
                # pin and falls through to the normal degrade below.
                noted = getattr(device_fn, "note_async_defect", None)
                if noted is not None and noted():
                    get_logger("exec").warning(
                        "deserialized executable failed asynchronously "
                        "(%s); recompiling in-process and retrying the "
                        "dispatch", str(e)[:200])
                    return self._dispatch_partition(
                        part, device_fn, skey, use_comp=use_comp,
                        stage=stage, packed=packed)
            if use_comp:
                get_logger("exec").warning(
                    "stage trace failed under compaction (%s: %s); "
                    "disabling compaction for the stage",
                    type(e).__name__, e)
                return self._redispatch_plain(part, skey, stage, t0,
                                              packed=packed)
            get_logger("exec").warning(
                "stage trace failed (%s: %s); falling back to the "
                "interpreter", type(e).__name__, e)
            self._note_demotion(skey, "trace", e, part)
            return (part, None, time.perf_counter() - t0, None)
        return (part, outs, time.perf_counter() - t0, launch)

    def _note_demotion(self, skey: str, phase: str, e: BaseException,
                       part=None) -> None:
        """A stage whose fast path failed to build or trace for a reason
        OTHER than NotCompilable still degrades to the interpreter, but
        loudly: the demotion lands in failure_log with the exception (a
        job that exits 0 with the device idle must be readable from the
        program's own records, not only from a log line)."""
        self._not_compilable.add(skey)
        self.failure_log.append({
            "stage": skey[:16], "phase": phase,
            "start_index": part.start_index if part is not None else 0,
            "rows": part.num_rows if part is not None else 0,
            "error": f"{type(e).__name__}: {e}",
            "action": "interpreter"})

    def _redispatch_plain(self, part: C.Partition, skey: str, stage, t0,
                          packed: bool = True):
        """Compaction couldn't trace: disable it for the stage and run the
        SAME partition through the plain compiled fn (an opt-in optimization
        must never demote work to the interpreter)."""
        self._compaction_off.add(skey.split("/", 1)[0])
        if stage is None:
            return (part, None, time.perf_counter() - t0, None)
        plain_fn, _ = self._build_stage_fn(stage, part.schema, skey, False,
                                           packed=packed)
        if plain_fn is None:
            return (part, None, time.perf_counter() - t0, None)
        res = self._dispatch_partition(part, plain_fn, skey, False, stage,
                                       packed=packed)
        return (res[0], res[1], time.perf_counter() - t0, res[3])

    # ------------------------------------------------------------------
    def _await_dispatch(self, stage, part: C.Partition, pending,
                        launch: _Launch, metrics: dict) -> None:
        """The job thread's wait for one dispatch's outputs: once, at the
        head of its collect and BEFORE any fetch, so the chip's seconds
        stay out of the `d2h:*` spans. The span opens on every collected
        dispatch, also where the outputs were ready on arrival, and says
        which side set this partition's pace: `ready` 1, the host did
        (nothing was waited for); 0, the chip did. `in_flight` 1 means
        nothing ran beside the host. The same `is_ready` poll with
        attribution on or off (`devprof.block_ready`): devprof only
        records. What the poll raises fails the task (`collect_one`'s
        retry -> elastic -> interpreter ladder), as a failed fetch does."""
        with TR.span("dispatch:device-wait", "exec") as _sp:
            _sp.set("in_flight", launch.in_flight)
            ready = DP.block_ready(pending)
            launch.ready_at = now = time.perf_counter()
            _sp.set("ready", int(ready))
        key = "dispatches_ready" if ready else "dispatches_waited"
        metrics[key] = metrics.get(key, 0) + 1
        # the chip runs a stage's dispatches one at a time: this one began
        # at its launch or when its predecessor ended, whichever was later.
        # Exact to the poll's 0.2 ms where the host waited; an upper bound
        # (`late`) where the outputs were ready when the host came
        DP.record_dispatch(stage.key(), now - max(launch.t, launch.after),
                           cold=launch.cold, rows=part.num_rows,
                           owner=id(self), late=ready)

    def _collect_partition(self, stage: TransformStage, part: C.Partition,
                           pending_outs, dispatch_s: float,
                           intermediate: bool = False,
                           launch: Optional[_Launch] = None,
                           regex_sites=()):
        import jax

        metrics: dict[str, float] = {}
        n = part.num_rows
        # rows needing the interpreter: input fallback slots, plus device-err
        fallback_idx: set[int] = set(part.fallback.keys())
        compiled_ok = np.zeros(n, dtype=np.bool_)
        out_arrays: dict[str, np.ndarray] = {}

        # plan-time resolve-tier decision + per-code row buffers shaped by
        # the analyzer's exception inventory (plan/physical.ResolvePlan):
        # which tiers run, and which bucket each error row lands in, are
        # decided BEFORE the fetch instead of re-derived per row after D2H
        rplan = stage.resolve_plan()
        bufs = rplan.new_buffers() if pending_outs is not None else None

        # deferred exception-plane records (runtime/excprof): a device
        # failure inside this attempt (e.g. the general tier's compiled
        # re-run) aborts the whole collect and the task-failure ladder
        # re-runs the partition — accounting must only commit for the
        # attempt that succeeds, or the retry double-counts every row
        # into the stage stats and the drift windows
        ex_defer: list = []

        # device error evidence per fallback row: idx -> (code, operator id).
        # General-tier codes overwrite fast-path ones (supertype decode is
        # the authoritative python-semantics run).
        device_codes: dict[int, tuple[int, int]] = {}
        src_map = None
        device_outs = pending_outs     # arrays eligible for the device view
        lazy_data = None               # device-resident data columns (deferred)
        if pending_outs is not None:
            t0 = time.perf_counter()
            if launch is None:      # a caller with outputs and no stamp
                launch = _Launch(t0, False)
            with TR.span("partition:collect-fast", "exec") as _sp:
                _sp.set("rows", n)
                self._await_dispatch(stage, part, pending_outs, launch,
                                     metrics)
                if intermediate and isinstance(pending_outs, dict) \
                        and type(self) is LocalBackend:
                    # handoff-bound partition: pull ONLY the control arrays
                    # ('#err'/'#keep'/compaction/fold lattice — a few KB)
                    # and leave the data columns on device. They reach the
                    # host later only if a slow path actually needs them;
                    # the clean fast path hands them straight to the next
                    # consumer (the boundary transfer was the largest
                    # single slice of zillow's wall on a slow D2H link;
                    # not measured on this machine)
                    import jax

                    ctrl = {k: v for k, v in pending_outs.items()
                            if k.startswith("#")}
                    outs = {k: np.asarray(v)
                            for k, v in jax.device_get(ctrl).items()}
                    xferstats.note_d2h(
                        sum(v.nbytes for v in outs.values()),
                        tag="handoff_ctrl")
                    lazy_data = {k: v for k, v in pending_outs.items()
                                 if not k.startswith("#")}
                else:
                    outs = _get_outs(pending_outs)
                # whether the function that ran compacted this batch (its
                # plan may be empty where `partition:dispatch` says 1)
                _sp.set("compacted", int("#rowidx" in outs))
            rowidx = outs.pop("#rowidx", None)
            ovf = outs.pop("#overflow", None)
            if rowidx is not None and bool(np.asarray(ovf)):
                # the sample under-estimated this filter's survivors and the
                # compaction bucket overflowed: results are unusable. Re-run
                # the partition without compaction and disable it for the
                # stage (reference analog: speculation failure -> general
                # path; here the failure is a SIZE speculation)
                from ..utils.logging import get_logger

                get_logger("exec").warning(
                    "compaction bucket overflow (stage %s); re-running "
                    "partition without compaction", stage.key()[:8])
                self._compaction_off.add(stage.key())
                metrics["compaction_reruns"] = 1
                packed = not intermediate   # keep the handoff's dict outs
                nkey = ("stagefn", stage.key() + "/" + part.schema.name,
                        False, packed)
                nfn = self.jit_cache.get_or_build(
                    nkey, lambda: self._jit_stage_fn(
                        stage.build_device_fn(part.schema,
                                              compaction=False),
                        packed=packed, tag=stage.key(),
                        n_ops=len(stage.ops)))
                batch = C.stage_partition(part, self.bucket_mode)
                relaunch = _Launch(
                    time.perf_counter(),
                    not self.jit_cache.was_traced(nkey, batch.spec()),
                    in_flight=launch.in_flight, after=launch.ready_at)
                pending2 = nfn(batch.arrays)
                self._await_dispatch(stage, part, pending2, relaunch,
                                     metrics)
                launch.ready_at = relaunch.ready_at
                outs = _get_outs(pending2)
                self.jit_cache.note_traced(nkey, batch.spec())
                outs.pop("#rowidx", None)
                outs.pop("#overflow", None)
                rowidx = None
                # the original compacted arrays overflowed and are garbage:
                # the device view must come from the re-run (and the
                # deferred-fetch fast path is off the table — the re-run
                # was fetched whole)
                device_outs = pending2
                lazy_data = None
            if rowidx is not None:
                # inverse map: original row i -> compact slot j (ascending
                # original order is preserved by compaction, so merge order
                # is unaffected)
                rowidx = np.asarray(rowidx)
                jpos = np.nonzero(rowidx < n)[0]
                src_map = np.full(n, -1, dtype=np.int64)
                src_map[rowidx[jpos]] = jpos
            metrics["fast_path_s"] = dispatch_s + time.perf_counter() - t0
            # the error lattice read into per-row codes and the resolve
            # plan's buckets
            with TR.span("resolve:codes", "exec") as _csp:
                err = np.asarray(outs.pop("#err"))[:n]
                keep = np.asarray(outs.pop("#keep"))[:n]
                rowvalid = np.zeros(n, dtype=np.bool_)
                if part.normal_mask is None:
                    rowvalid[:] = True
                else:
                    rowvalid[:] = part.normal_mask
                err_rows = rowvalid & (err != 0)
                err_idx = np.nonzero(err_rows)[0]
                fallback_idx.update(err_idx.tolist())
                # packed lattice value: class code | operator << 8, the
                # operator as its position in the stage on the device and
                # as THIS job's operator id from here on. Read by the
                # no-resolver exact exit below AND the general-tier gate:
                # a row whose fast-path code is already an exact Python
                # class decoded fine under the normal case — the general
                # re-run cannot change its outcome, so it skips that tier
                # either way.
                codes = stage.op_ids_of_lattice(err[err_idx])
                device_codes.update(
                    zip(err_idx.tolist(), unpack_device_codes(codes)))
                bufs.add_many(err_idx, codes)
                if EX.enabled():
                    # exception-plane unpack accounting (runtime/excprof):
                    # the raw packed lattice carries code + operator id, so
                    # per-stage x per-op x per-code counts come vectorized
                    # off the same array the resolve buckets consumed
                    ex_defer.append((EX.note_device, (stage.key(), n, codes),
                                     {"fallback_rows": len(part.fallback),
                                      "owner": id(self)}))
                if regex_sites:
                    metrics["regex_fallback_rows"] = int(np.count_nonzero(
                        (err[err_idx] & 0xFF)
                        == int(ExceptionCode.REGEXFALLBACK)))
                compiled_ok = rowvalid & keep & (err == 0)
                fold_vals = []
                while f"#fold{len(fold_vals)}" in outs:
                    fold_vals.append(outs.pop(f"#fold{len(fold_vals)}"))
                foldok = outs.pop("#foldok", None)
                out_arrays = {k: np.asarray(v) for k, v in outs.items()}
                if _csp is not TR.NOOP:
                    _csp.set("rows", len(err_idx))
        else:
            # whole partition interpreted (UDF not compilable / forced /
            # no normal-case rows)
            metrics["fast_path_s"] = dispatch_s
            fallback_idx.update(range(n))
            if EX.enabled():
                ex_defer.append((EX.note_device, (stage.key(), n, None),
                                 {"fallback_rows": n, "owner": id(self)}))

        # ---- compiled general-case tier (ResolveTask resolve_f analog) ----
        # gated by the PLAN-time tier decision: when the inventory proves
        # the general tier can't retire anything (no widened decode in the
        # stage), the build attempt is skipped outright — it used to cost
        # one doomed NotCompilable trace per (stage, schema) to learn this
        resolved: dict[int, Row] = {}
        if fallback_idx and pending_outs is not None \
                and rplan.use_general and not self.interpret_only:
            t0 = time.perf_counter()
            n_before = len(fallback_idx)
            with TR.span("resolve:general", "exec") as _sp:
                _sp.set("rows", n_before)
                faults.maybe("resolve", point="general")   # chaos
                # checkpoint: a hang (delay=) INSIDE the span injects pure
                # resolve-path latency — the lever the latency-budget
                # acceptance uses to prove whyslow, the dashboard panel
                # and serve:slow-job all blame the same bucket
                # (runtime/critpath)
                self._general_case_pass(stage, part, fallback_idx, resolved,
                                        device_codes, buffers=bufs,
                                        span=_sp)
                _sp.set("resolved", len(resolved))
            dt = time.perf_counter() - t0
            metrics["general_path_s"] = dt
            if EX.enabled():
                ex_defer.append((EX.note_tier,
                                 (stage.key(), "general", n_before,
                                  n_before - len(fallback_idx), dt),
                                 {"owner": id(self)}))

        # ---- exact device exceptions (no-resolver fast exit) --------------
        # When the stage carries no resolver/ignore, a row whose device code
        # is an exact Python exception class (codes 1-9; internal/suspect
        # codes are >= 100) needs no interpreter re-run: class + operator
        # come straight off the lattice. The reference likewise emits
        # exception partitions from compiled code and only runs ResolveTask
        # when there is something to resolve.
        exc_by_row: dict[int, ExceptionRecord] = {}
        if fallback_idx and not stage.has_resolvers \
                and not self.interpret_only:
            # one exception record a row the device classified exactly
            with TR.span("resolve:exact-exit", "exec") as _esp:
                if bufs is not None and not rplan.use_general:
                    # the exact-class rows sit in their plan-time buckets
                    # already — no per-row dict probe + class lookup here
                    exact = [(i, op_id, code, exception_name(code))
                             for i, code, op_id in bufs.exact_rows()
                             if i in fallback_idx]
                else:
                    # general tier ran: its verdicts superseded fast-path codes
                    # in device_codes, so classify from there
                    exact = []
                    for i in sorted(fallback_idx):
                        code_op = device_codes.get(i)
                        if code_op is None:
                            continue
                        code, op_id = code_op
                        if exception_class_for_code(code) is not None:
                            exact.append((i, op_id, code,
                                          exception_name(code)))
                # decode a handful of rows so history previews stay
                # informative; counts only need the class name
                sample = {}
                if exact:
                    sidx = [i for i, _, _, _ in exact[:5]]
                    sample = dict(zip(sidx, C.decode_rows(part, sidx)))
                for i, op_id, code, name in exact:
                    exc_by_row[i] = ExceptionRecord(op_id, name, sample.get(i))
                    fallback_idx.discard(i)
                if EX.enabled() and exact:
                    ex_defer.append((EX.note_outcomes,
                                     (stage.key(),
                                      [(code, op_id)
                                       for _, op_id, code, _ in exact],
                                      "exact-exit"), {"owner": id(self)}))
                    for i, _op, code, _nm in exact[:5]:
                        if i in sample:
                            ex_defer.append((EX.sample_row,
                                             (stage.key(), code, sample[i]),
                                             {}))
                if _esp is not TR.NOOP:
                    _esp.set("rows", len(exc_by_row))

        # ---- interpreter path (ResolveTask analog) ------------------------
        # one compiled closure chain per stage + bulk row decode: no per-row
        # op dispatch (reference: PythonPipelineBuilder.cc)
        t0 = time.perf_counter()
        if fallback_idx:
            with TR.span("resolve:interpreter", "exec") as _sp:
                # `boxed`: rows boxed at ingest, which never rode the
                # columnar path (the rest fell here off the device)
                _sp.set("rows", len(fallback_idx)).set(
                    "boxed", len(fallback_idx & part.fallback.keys()))
                pipeline = stage.python_pipeline(part.user_columns)
                order = sorted(fallback_idx)
                ex_on = EX.enabled()
                interp_pairs: list = []     # (final code, op_id) per row
                code_counts: dict = {}      # exc name -> n (span attr)
                n_exc = 0
                row_sample_budget = 16      # lock-taking sample_row calls
                # per partition (the per stage x code K-bound lives
                # inside excprof; this keeps a full-fallback partition
                # from probing the lock once per row)
                for i, row in zip(order, C.decode_rows(part, order)):
                    status, payload = pipeline(row)
                    if status == "ok":
                        resolved[i] = payload
                    elif status == "exc":
                        op_id, exc_name, value = payload[:3]
                        trace = payload[3] if len(payload) > 3 else None
                        exc_by_row[i] = ExceptionRecord(op_id, exc_name,
                                                        value, trace)
                        n_exc += 1
                        if ex_on:
                            code = EX.code_for_name(exc_name)
                            interp_pairs.append((code, op_id))
                            if row_sample_budget > 0:
                                row_sample_budget -= 1
                                ex_defer.append((EX.sample_row,
                                                 (stage.key(), code,
                                                  value), {}))
                            code_counts[exc_name] = \
                                code_counts.get(exc_name, 0) + 1
                        continue
                    if ex_on:
                        # retired on the interpreter (resolved or
                        # filtered): attribute the row's ORIGINAL device
                        # code to this tier — that is the code that fell
                        # all the way down
                        code, op_id = device_codes.get(
                            i, (int(ExceptionCode.PYTHON_FALLBACK), 0))
                        interp_pairs.append((code, op_id))
                        if row_sample_budget > 0:
                            # the INPUT row that fell to this tier even
                            # though it resolved — "why did row X reach
                            # the interpreter" from the dashboard
                            row_sample_budget -= 1
                            ex_defer.append((EX.sample_row,
                                             (stage.key(), code, row), {}))
                dt = time.perf_counter() - t0
                if ex_on:
                    ex_defer.append((EX.note_outcomes,
                                     (stage.key(), interp_pairs,
                                      "interpreter"), {"owner": id(self)}))
                    ex_defer.append((EX.note_tier,
                                     (stage.key(), "interpreter",
                                      len(order), len(order) - n_exc, dt),
                                     {"owner": id(self)}))
                if _sp is not TR.NOOP:
                    _sp.set("resolved", len(order) - n_exc)
                    if code_counts:
                        _sp.set("codes", ",".join(
                            f"{k}:{v}" for k, v in
                            sorted(code_counts.items())[:6]))
        metrics["slow_path_s"] = time.perf_counter() - t0

        outp = None
        with TR.span("partition:merge", "exec") as _msp:
            if lazy_data is not None and not resolved:
                # no python-spliced rows: the output partition can stay
                # device-resident end to end (lazy host leaves + gathered
                # view)
                outp = self._lazy_merge(stage, part, compiled_ok, lazy_data,
                                        src_map)
            if _msp is not TR.NOOP:
                _msp.set("path", "lazy" if outp is not None
                         else "resolved" if resolved else "host")
            if outp is None:
                if lazy_data is not None:
                    # a slow path touched this partition (or the lazy layout
                    # didn't qualify): pull the data columns after all
                    with TR.span("d2h:merge-fetch", "xfer") as _fsp:
                        if _fsp is not TR.NOOP:
                            _fsp.set("ready", int(_all_ready(lazy_data)))
                        out_arrays = {k: np.asarray(v) for k, v in
                                      _get_outs(lazy_data).items()}
                        if _fsp is not TR.NOOP:
                            _fsp.set("bytes", sum(
                                v.nbytes for v in out_arrays.values()))
                outp = self._merge(stage, part, compiled_ok, out_arrays,
                                   resolved, src_map=src_map)
                if intermediate and device_outs is not None and not resolved \
                        and not outp.fallback \
                        and getattr(outp, "_gather_src", None) is not None:
                    self._attach_device_view(outp, device_outs)
            _msp.set("rows", outp.num_rows)
        if pending_outs is not None and fold_vals and foldok is not None \
                and not resolved and not outp.fallback \
                and getattr(stage, "fold_op", None) is not None:
            # fused aggregate partials are exact only when every output row
            # came off the device (python-resolved/boxed rows would be
            # missing from them)
            ok_np = np.asarray(foldok)[:n]
            badmask = compiled_ok & ~ok_np
            kept_rank = np.cumsum(compiled_ok) - 1
            outp.fold_partials = (
                stage.fold_op.id,
                tuple(v.item() for v in fold_vals),
                [int(r) for r in kept_rank[badmask]])
        # this attempt produced the partition's output: order its
        # exceptions and commit its exception-plane records (a failure
        # above left them unrecorded for the task-failure ladder's re-run
        # to record afresh)
        with TR.span("resolve:record", "exec") as _rsp:
            exceptions = [exc_by_row[i] for i in sorted(exc_by_row)]
            for fn, a, kw in ex_defer:
                fn(*a, **kw)
            if _rsp is not TR.NOOP:
                _rsp.set("rows", len(exceptions))
        return outp, exceptions, metrics

    # ------------------------------------------------------------------
    def _general_case_pass(self, stage: TransformStage, part: C.Partition,
                           fallback_idx: set, resolved: dict,
                           device_codes: Optional[dict] = None,
                           local_jit: bool = False,
                           buffers=None, span=TR.NOOP) -> None:
        """Compiled middle tier: re-run normal-case-violating rows through
        the stage fn traced under the GENERAL-CASE schema (Option/supertype
        widened decode). Rows it completes fold back like resolved python
        rows — but their compute stayed vectorized; only rows that STILL err
        reach the per-row interpreter (reference: StageBuilder.cc:1145
        generateResolveCodePath, ResolveTask.h resolve_f-before-interpreter).
        `span` is the caller's `resolve:general`, which learns where the
        batch ran (`path`), the rows it carried (`rows`, of those on offer),
        its padded rows (`batch`) and `first_call`.
        """
        import jax

        gkey = "general/" + stage.key() + "/" + part.schema.name \
            + ("/local" if local_jit else "")
        if gkey in self._not_compilable:
            return
        # input-boxed rows can't ride the columnar general path; rows whose
        # fast-path code is already an exact Python exception class decoded
        # fine under the normal case — a supertype re-run reproduces the
        # same exception, so they skip straight past this tier
        cand_info: dict[int, tuple] = {}   # idx -> (code, op_id) for the
        # exception-plane tier attribution (runtime/excprof)
        if buffers is not None:
            # plan-time buckets: the internal-coded candidate set was
            # grouped at D2H unpack, no per-row re-classification
            cand_info = {i: (code, op_id)
                         for i, code, op_id in buffers.internal_rows()
                         if i in fallback_idx and i not in part.fallback}
            cand = sorted(cand_info)
        else:
            dc = device_codes or {}
            cand = sorted(
                i for i in fallback_idx
                if i not in part.fallback
                and exception_class_for_code(dc.get(i, (0, 0))[0]) is None)
            cand_info = {i: dc.get(i, (0, 0)) for i in cand}
        if not cand:
            return
        # a small violation set on an accelerator backend resolves on the
        # HOST CPU executable instead: the fixed dispatch+transfer tax of
        # the device round-trip dwarfs the compute for a few thousand rows
        # (on a four-chip v5e mesh 34.5 ms a partition at a 7,168-row
        # batch, put and fetch leaf by leaf, against 15 ms on the host
        # and 14 ms at 3,584; the routes meet at the option's default,
        # 41 ms each at 16,384 rows: PERF.md section 6, PR 29; reference
        # contrast: resolve tasks share the driver's threads,
        # ResolveTask.h:31-98). Larger sets, and every set where the rows
        # span processes (SPMD lockstep), take the backend's own dispatch
        host_resolve = (
            not local_jit and self.host_resolve
            and len(cand) <= self.options.get_int(
                "tuplex.tpu.hostResolveRows", 16384)
            and _host_cpu_beside_accelerator())
        gckey = ("stagefn", gkey, "cpu") if host_resolve \
            else ("stagefn", gkey)
        try:
            # local_jit: the caller's rows are HOST-LOCAL (host-block
            # resolve) — the mesh dispatch would violate SPMD lockstep,
            # so build a plain single-host jit instead
            gfn = self.jit_cache.get_or_build(
                gckey,
                lambda: ((lambda f: _CpuJit(f, tag=stage.key()))
                         if host_resolve else
                         jax.jit if local_jit else
                         (lambda f: self._jit_stage_fn(
                             f, tag=stage.key())))(
                    stage.build_device_fn(part.schema, general=True)))
        except NotCompilable:
            self._not_compilable.add(gkey)
            return
        idx = np.asarray(cand, dtype=np.int64)
        k = len(idx)
        sub = C.gather_partition(part, np.arange(k, dtype=np.int64), idx, k)
        sub.fallback = {}
        sub.normal_mask = None
        # the batch's shape follows from the partition's own staging and
        # not from the rows that deviated: the string widths are the
        # partition's (gather_partition keeps them), the rows pad to
        # general_batch_size (padded rows carry #rowvalid=False), so a new
        # file of the same distribution finds its executable stored
        batch = C.stage_partition(
            sub, self.bucket_mode, force_b=C.general_batch_size(
                k, part.num_rows, self.bucket_mode))
        cache_key = gckey
        spec = batch.spec()
        first_call = not self.jit_cache.was_traced(cache_key, spec)
        path = "host-cpu" if host_resolve \
            else "device" if local_jit else self.dispatch_path
        if span is not TR.NOOP:
            # `rows`: what the batch carries, not the rows on offer
            span.set("path", path).set("rows", k).set("batch", batch.b) \
                .set("first_call", int(first_call))
        try:
            outs = gfn(batch.arrays)
            self.jit_cache.note_traced(cache_key, spec)
            if not host_resolve and not isinstance(gfn, PackedStageFn):
                # per-leaf staging uploads the tier's batch, as the fast
                # path's in _dispatch_partition ("leaf_stage"); a packed
                # dispatch notes its own single buffer and the host-CPU
                # executable uploads nothing
                xferstats.note_h2d(C.host_nbytes(batch.arrays),
                                   tag="general_stage")
        except Exception as e:
            if not first_call:
                raise
            from ..utils.logging import get_logger

            get_logger("exec").warning(
                "general-case trace failed (%s: %s); rows stay on the "
                "interpreter", type(e).__name__, e)
            self._not_compilable.add(gkey)
            return
        outs = _get_outs(outs)
        err = np.asarray(outs.pop("#err"))[:k]
        keep = np.asarray(outs.pop("#keep"))[:k]
        ok = err == 0
        if device_codes is not None and not stage.has_resolvers:
            # the general tier's verdict supersedes the fast path's: its
            # supertype decode removes normal-case artifacts
            bad_j = np.nonzero(~ok)[0]
            codes = stage.op_ids_of_lattice(err[bad_j])
            device_codes.update(
                zip(idx[bad_j].tolist(), unpack_device_codes(codes)))
        if not ok.any():
            return
        out_arrays = {kk: np.asarray(v) for kk, v in outs.items()}
        from ..plan.physical import runtime_output_columns

        out_cols = runtime_output_columns(part.schema, stage.ops)
        outp = C.partition_from_result_arrays(out_arrays, k,
                                              columns=out_cols)
        vals = C.partition_to_pylist(outp)
        cols = outp.user_columns
        single = len(outp.schema.types) == 1
        retired_pairs: list = []
        for j in range(k):
            if not ok[j]:
                continue
            i = int(idx[j])
            fallback_idx.discard(i)
            retired_pairs.append(cand_info.get(i, (0, 0)))
            if keep[j]:
                v = vals[j]
                resolved[i] = Row((v,), cols) if single else Row(v, cols)
            # else: filtered out on the general path — row emits nothing
        if retired_pairs and EX.enabled():
            # which codes the compiled general tier RETIRED (the
            # vectorized re-run absorbed them before the interpreter)
            EX.note_outcomes(stage.key(), retired_pairs, "general",
                             owner=id(self))

    # ------------------------------------------------------------------
    def _merge(self, stage: TransformStage, part: C.Partition,
               compiled_ok: np.ndarray, out_arrays: dict,
               resolved: dict[int, Row],
               src_map: np.ndarray | None = None) -> C.Partition:
        """Positional merge-in-order (reference: ResolveTask.cc:238-283).

        The output schema is derived from the ACTUAL device arrays (never the
        sample-speculated logical schema) so fast-path results can't be
        reinterpreted under a mismatched layout; with no compiled rows the
        resolved python rows are re-encoded from scratch."""
        n = part.num_rows
        if not resolved and out_arrays:
            # fast path (no python-resolved rows to splice): the emit set is
            # exactly the compiled_ok positions — skip the per-row loop
            # (0.3s/300k rows measured on TPC-H Q1)
            from ..plan.physical import runtime_output_columns

            comp_src = np.nonzero(compiled_ok)[0].astype(np.int64)
            m = int(comp_src.size)
            out_cols = runtime_output_columns(part.schema, stage.ops)
            n_full = n if src_map is None else \
                int(next(iter(out_arrays.values())).shape[0])
            full = C.partition_from_result_arrays(
                out_arrays, n_full, columns=out_cols,
                start_index=part.start_index)
            if src_map is not None and comp_src.size:
                comp_src = src_map[comp_src]
            outp = C.gather_partition(
                full, np.arange(m, dtype=np.int64), comp_src, m)
            outp._gather_src = comp_src   # device-view handoff indices
            return outp
        with TR.span("merge:splice", "exec") as sp:
            if sp is not TR.NOOP:
                sp.set("rows", len(resolved))
            return self._splice(stage, part, compiled_ok, out_arrays,
                                resolved, src_map)

    def _splice(self, stage: TransformStage, part: C.Partition,
                compiled_ok: np.ndarray, out_arrays: dict,
                resolved: dict[int, Row],
                src_map: np.ndarray | None) -> C.Partition:
        """`_merge` where rows were resolved off the compiled path: the
        emit order, the compiled rows gathered into it and the resolved
        ones folded in (boxed into `fallback` where they do not fit)."""
        n = part.num_rows
        emit_rows: list[tuple[int, Optional[int], Optional[Row]]] = []
        # (orig_idx, compiled_src or None, resolved Row or None)
        for i in range(n):
            if i in resolved:
                emit_rows.append((i, None, resolved[i]))
            elif compiled_ok[i]:
                emit_rows.append((i, i, None))
        m = len(emit_rows)

        if not out_arrays:
            # interpreter-only: build straight from python rows. Schema
            # derives from the RUNTIME rows (their column names/types), not
            # sample speculation — projection/segmentation may have changed
            # the shape.
            values = [row.unwrap() if len(row.values) == 1
                      else tuple(row.values)
                      for (_, _, row) in emit_rows]
            rows_only = [row for (_, _, row) in emit_rows]
            schema = _schema_from_rows(rows_only) or \
                _normalized_output_schema(stage)
            outp = C.build_partition(values, schema,
                                     start_index=part.start_index)
            return outp

        from ..plan.physical import runtime_output_columns

        out_cols = runtime_output_columns(part.schema, stage.ops)
        n_full = n if src_map is None else \
            int(next(iter(out_arrays.values())).shape[0])
        full = C.partition_from_result_arrays(
            out_arrays, n_full, columns=out_cols,
            start_index=part.start_index)
        comp_out = np.asarray([k for k, (_, src, _) in enumerate(emit_rows)
                               if src is not None], dtype=np.int64)
        comp_src = np.asarray([src for (_, src, _) in emit_rows
                               if src is not None], dtype=np.int64)
        if src_map is not None and comp_src.size:
            # compacted device outputs: original position -> compact slot
            comp_src = src_map[comp_src]
        outp = C.gather_partition(full, comp_out, comp_src, m)
        out_schema = outp.schema

        res_ks = []
        res_vals = []
        for k, (_, src, row) in enumerate(emit_rows):
            if row is None:
                continue
            res_ks.append(k)
            res_vals.append(row.unwrap() if len(out_schema.columns) == 1
                            else tuple(row.values))
        if not res_ks:
            return outp
        if _bulk_fold_rows(outp.leaves, out_schema,
                           np.asarray(res_ks, dtype=np.int64), res_vals):
            return outp
        normal_mask = np.ones(m, dtype=np.bool_)
        fallback: dict[int, Any] = {}
        for k, value in zip(res_ks, res_vals):
            if _try_fold_row(outp.leaves, out_schema, k, value):
                continue
            normal_mask[k] = False
            fallback[k] = value
        if fallback:
            outp.normal_mask = normal_mask
            outp.fallback = fallback
        return outp


def _avals_spec(avals: dict) -> tuple:
    """`Batch.spec()` of the batch these avals describe (JitCache's
    traced-spec bookkeeping)."""
    return tuple(sorted((k, tuple(v.shape), str(v.dtype))
                        for k, v in avals.items()))


def _prefetch_iter(it, depth: int):
    """Producer-thread wrapper: source loading (Arrow read/decode) overlaps
    with device compute + merge (reference: Executor.h WorkQueue IO overlap;
    the interleaveIO analog). Bounded queue so memory stays capped."""
    import queue
    import threading

    q: "queue.Queue" = queue.Queue(maxsize=depth)
    stop = threading.Event()
    _END = object()
    # inherit the consumer thread's tenant scoping onto the producer:
    # span-stream tag (runtime/tracing) and counter scope (xferstats) are
    # THREAD-local, so source-load spans / ingest byte counters recorded
    # on this helper thread used to land untagged during serve — only
    # dispatch-path events were reliably tenant-tagged
    stream = TR.current_stream()
    scope = xferstats.current_scope()
    cause = TR.handoff()     # the producer's reads name the consuming job

    def put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def produce():
        if stream is not None:
            TR.set_stream(stream)
        if scope is not None:
            xferstats.set_scope(scope)
        try:
            with TR.adopt(cause):
                for item in it:
                    if not put(item):
                        return   # consumer stopped early (take-limit)
            put(_END)
        except BaseException as e:  # surface source errors on the consumer
            put(e)

    t = threading.Thread(target=produce, daemon=True,
                         name="tuplex-source-prefetch")
    t.start()
    try:
        while True:
            with TR.span("source:wait", "io"):
                item = q.get()
            if item is _END:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()   # unblock the producer if we exited early


def _schema_from_rows(rows: list[Row]) -> Optional[T.RowType]:
    """Normal-case schema speculated from actual interpreter-produced rows.

    Types from a bounded SAMPLE (speculation, like every other schema here):
    rows outside the sampled normal case are boxed by build_partition's
    fallback path, so a capped scan is safe and O(1) in dataset size."""
    rows = [r for r in rows if r is not None]
    if not rows:
        return None
    k = len(rows[0].values)
    if any(len(r.values) != k for r in rows):
        return None
    cols = rows[0].columns
    if cols is None or len(cols) != k:
        cols = tuple(f"_{i}" for i in range(k))
    sample = rows[:256]
    types = []
    for ci in range(k):
        nc, _, _ = T.normal_case_type([r.values[ci] for r in sample])
        if nc is T.UNKNOWN:
            return None
        types.append(nc)
    return T.row_of(cols, types)


def _normalized_output_schema(stage: TransformStage) -> T.RowType:
    """Logical output schema with the stage's user column names applied."""
    s = stage.output_schema
    cols = stage.output_columns
    if cols and len(cols) == len(s.types):
        return T.row_of(cols, s.types)
    return s


def _truncate_partition(p: C.Partition, k: int) -> C.Partition:
    if k >= p.num_rows:
        return p
    leaves = {}
    for path, leaf in p.leaves.items():
        if isinstance(leaf, C.NumericLeaf):
            leaves[path] = C.NumericLeaf(
                leaf.data[:k], None if leaf.valid is None else leaf.valid[:k])
        elif isinstance(leaf, C.StrLeaf):
            leaves[path] = C.StrLeaf(
                leaf.bytes[:k], leaf.lengths[:k],
                None if leaf.valid is None else leaf.valid[:k])
        elif isinstance(leaf, C.NullLeaf):
            leaves[path] = C.NullLeaf(k)
        else:
            leaves[path] = C.ObjectLeaf(leaf.values[:k])
    return C.Partition(
        schema=p.schema, num_rows=k, leaves=leaves,
        normal_mask=None if p.normal_mask is None else p.normal_mask[:k],
        fallback={i: v for i, v in p.fallback.items() if i < k},
        start_index=p.start_index)


def _bulk_fold_rows(leaves: dict, schema: T.RowType,
                    ks: "np.ndarray", values: list) -> bool:
    """All-or-nothing vectorized fold-back of resolved python rows into
    columnar slots. Returns False (writing nothing) when any value doesn't
    conform exactly — the caller then runs the per-row path, which handles
    partial conformance by boxing. ~5x cheaper than per-row _try_fold_row
    on dual-mode-heavy data (measured 0.57s/3.3k rows on flights)."""
    cols = schema.columns
    multi = len(cols) > 1
    rows = []
    for v in values:
        rt = v if multi else ((v,) if not (isinstance(v, tuple)
                                           and len(v) == 1) else v)
        if multi and not (isinstance(rt, tuple) and len(rt) == len(cols)):
            return False
        rows.append(rt)
    cols_cache: list = []
    bytes_cache: dict = {}
    for ci, ct in enumerate(schema.types):
        base = ct.without_option() if ct.is_optional() else ct
        if isinstance(base, T.TupleType):
            return False   # nested layouts: per-row path
        col = [r[ci] for r in rows]
        cols_cache.append(col)
        if not all(T.python_value_conforms(v, ct) for v in col):
            return False
        leaf = leaves[str(ci)]
        if isinstance(leaf, C.StrLeaf):
            bs = [b"" if v is None else v.encode("utf-8") for v in col]
            bytes_cache[ci] = bs
            if max(map(len, bs), default=0) > leaf.bytes.shape[1]:
                return False
        elif not isinstance(leaf, C.NumericLeaf):
            return False
    # every value conforms: write
    for ci, ct in enumerate(schema.types):
        leaf = leaves[str(ci)]
        col = cols_cache[ci]
        if isinstance(leaf, C.StrLeaf):
            bs = bytes_cache[ci]
            w = leaf.bytes.shape[1]
            block = np.zeros((len(bs), w), dtype=np.uint8)
            for j, b in enumerate(bs):
                if b:
                    block[j, : len(b)] = np.frombuffer(b, np.uint8)
            leaf.bytes[ks] = block
            leaf.lengths[ks] = np.fromiter(map(len, bs), np.int32,
                                           count=len(bs))
            if leaf.valid is not None:
                leaf.valid[ks] = np.fromiter(
                    (v is not None for v in col), np.bool_, count=len(col))
        else:
            if leaf.valid is not None:
                leaf.valid[ks] = np.fromiter(
                    (v is not None for v in col), np.bool_, count=len(col))
                leaf.data[ks] = np.asarray(
                    [0 if v is None else v for v in col], dtype=leaf.data.dtype)
            else:
                leaf.data[ks] = np.asarray(col, dtype=leaf.data.dtype)
    return True


def _try_fold_row(leaves: dict, schema: T.RowType, k: int, value: Any) -> bool:
    """Write a resolved python row into the columnar slots if it conforms."""
    multi = len(schema.columns) > 1
    row_tuple = value if multi else (value,)
    if multi and not (isinstance(row_tuple, tuple)
                      and len(row_tuple) == len(schema.columns)):
        return False
    if not multi and isinstance(value, tuple) and len(value) == 1:
        row_tuple = value
    for rv, ct in zip(row_tuple, schema.types):
        if not T.python_value_conforms(rv, ct):
            return False
    for ci, (ct, rv) in enumerate(zip(schema.types, row_tuple)):
        for p, lv in C._leaf_paths_for_value(str(ci), ct, rv):
            leaf = leaves[p]
            if isinstance(leaf, C.StrLeaf):
                b = lv.encode("utf-8") if lv is not None else b""
                if len(b) > leaf.bytes.shape[1]:
                    return False  # wider than the column: keep boxed
                leaf.bytes[k, :] = 0
                if b:
                    leaf.bytes[k, : len(b)] = np.frombuffer(b, np.uint8)
                leaf.lengths[k] = len(b)
                if leaf.valid is not None:
                    leaf.valid[k] = lv is not None
            elif isinstance(leaf, C.NumericLeaf):
                if leaf.valid is not None:
                    leaf.valid[k] = lv is not None
                    leaf.data[k] = 0 if lv is None else lv
                else:
                    leaf.data[k] = lv if not isinstance(lv, bool) or \
                        leaf.data.dtype == np.bool_ else int(lv)
    return True


# interpreter pipeline: see compiler/pypipeline.build_python_pipeline
# (PythonPipelineBuilder + ResolveTask analog), driven per stage above.
