"""Job-service data model: requests, handles, and the per-job runner.

A submitted pipeline travels as a ``JobRequest`` whose stages are the SAME
stage-spec serialization the serverless fan-out ships to workers
(exec/serverless.serialize_stage / rebuild_stage) — UDF sources + captured
globals + authoritative schemas, with file sources referenced by path and
memory sources staged to the scratch dir as native-format parts (the
exec/worker.py staged-parts protocol). That makes a request picklable end
to end, so the same object serves the in-process ``Context.submit()`` path
and the scratch-dir wire protocol (serve/client.py).

Stages the spec can't carry (joins, aggregates — the driver-side merge
tier in the serverless analog) ride as LIVE stage objects for in-process
submissions; the wire client rejects them.

Each admitted job gets its own ``_JobRunner``: a private LocalBackend over
the SHARED warm device whose MemoryManager budget is the job's memory
budget (runtime/spill.py enforces it by LRU spill — a budget-blowing job
degrades to disk instead of OOM-ing the process), while every stage
executable still dedups process-wide through exec/compilequeue's
content-addressed store — N isomorphic jobs cost ~1 compile set.
"""

from __future__ import annotations

import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Optional

from ..core.errors import TuplexException
from ..utils.logging import get_logger

log = get_logger("tuplex_tpu.serve")


class JobRejected(TuplexException):
    """Admission refused (queue full past the admission timeout, memory
    budget above the service cap, unshippable wire request...). The
    message states the reason — rejection is part of the protocol, never
    a silent drop."""


class QueueFull(JobRejected):
    """The depth-bound admission queue had no slot within the allowed
    wait. Distinguished from terminal rejections because it is the one
    RETRYABLE kind — the wire loop polls with a zero wait and retries
    until the admission timeout instead of blocking its poll thread."""


class JobFailed(TuplexException):
    """Raised by ``JobHandle.result()`` when the job's execution failed."""


def transient_failure(exc: BaseException) -> bool:
    """Whether a job failure is worth RETRYING (the serve retry ladder's
    one classification decision). Transient = the run environment broke —
    a killed/deadlined compile, a device or dispatch runtime error, an
    injected transient fault, I/O flaking — so a fresh attempt on the
    same warm device can succeed. Deterministic = the job itself is wrong
    (user-code exceptions the resolvers didn't absorb, malformed
    requests, plan errors): retrying burns device time to fail
    identically, so it short-circuits with the clear error instead.

    Unknown exception types default to DETERMINISTIC: a retry loop that
    guesses "transient" on everything turns every poison job into
    retryCount poison jobs."""
    from ..exec.compilequeue import CompileTimeout
    from ..runtime.faults import FaultInjected

    if isinstance(exc, FaultInjected):
        return exc.transient
    if isinstance(exc, CompileTimeout):
        return True
    if isinstance(exc, (FileNotFoundError, PermissionError,
                        IsADirectoryError, NotADirectoryError)):
        return False            # bad paths/permissions recur identically
    if isinstance(exc, (ConnectionError, BrokenPipeError, TimeoutError,
                        OSError)):
        return True             # I/O flaking: a fresh attempt can win
    if isinstance(exc, TuplexException):
        return False            # framework-classified user/plan errors
    name = type(exc).__name__
    if name in ("XlaRuntimeError", "RuntimeError", "InternalError"):
        msg = str(exc)
        return any(p in msg for p in (
            "RESOURCE_EXHAUSTED", "DEADLINE", "UNAVAILABLE", "INTERNAL",
            "ABORTED", "device", "Device", "dispatch"))
    return False


#: job lifecycle states
QUEUED = "queued"
RUNNING = "running"
DONE = "done"
FAILED = "failed"
REJECTED = "rejected"
CANCELLED = "cancelled"


@dataclass
class JobRequest:
    """One pipeline submission. ``stages`` entries are dicts with one of:

    * ``{"spec": <serialize_stage dict>, "files": [...] | None}`` — a
      transform stage over a file source (or a mid-pipeline stage:
      ``files`` None and no ``indir``);
    * ``{"spec": ..., "indir": path}`` — first stage whose memory input
      was staged to scratch as native-format parts;
    * ``{"live": <stage object>}`` — in-process only (joins/aggregates).
    """

    stages: list
    name: str = "job"
    tenant: str = "default"
    options: dict = field(default_factory=dict)   # per-job option overrides
    memory_budget: Optional[int] = None           # bytes; None -> service
                                                  # default (tuplex.serve.
                                                  # jobMemory)
    weight: Optional[int] = None                  # DRR weight; None -> the
                                                  # tenant's configured one
    collect: bool = True                          # materialize result rows

    def wire_safe(self) -> bool:
        """Whether every stage travels by spec (picklable wire form)."""
        return all("live" not in e for e in self.stages)


class JobHandle:
    """Caller-side view of a submitted job (the Lambda 'invocation id'
    analog). Thread-safe: state flips under the service condition, waits
    ride the same condition."""

    def __init__(self, record, service):
        self._rec = record
        self._svc = service

    # -- identity ----------------------------------------------------------
    @property
    def id(self) -> str:
        return self._rec.id

    @property
    def tenant(self) -> str:
        return self._rec.request.tenant

    @property
    def name(self) -> str:
        return self._rec.request.name

    # -- state -------------------------------------------------------------
    @property
    def state(self) -> str:
        return self._rec.state

    @property
    def error(self) -> Optional[str]:
        return self._rec.error

    @property
    def metrics(self):
        """Per-job api.Metrics — stage records land here, never on another
        tenant's object."""
        return self._rec.metrics

    @property
    def stats(self) -> dict:
        """Scheduler-side accounting: turns consumed, global turn at
        completion, queue wait seconds, and the job's memory footprint
        against its budget (its own MemoryManager — runtime/spill.py)."""
        out = dict(self._rec.stats)
        runner = self._rec.runner
        if runner is not None:
            mm = runner.backend.mm
            out["resident_bytes"] = mm.resident_bytes()
            out["budget_bytes"] = mm.budget
            out.update(mm.metrics())
        return out

    def counters(self) -> dict:
        """This job's scoped xferstats family (bumps made on its
        executing thread: d2h/h2d/spill plus inline-dispatch compile
        counters) — isolated from other tenants. Snapshotted onto the
        record at completion (the live registry entry is released so the
        service doesn't grow per job served)."""
        return self._rec._counters()

    def trace_events(self) -> list:
        """This job's span stream (runtime/tracing events recorded under
        its stream tag). Empty unless tracing is enabled."""
        from ..runtime import tracing

        return tracing.events_for_stream(self._rec.id)

    def exceptions(self) -> list:
        return list(self._rec.exceptions)

    def exc_profile(self) -> dict:
        """The TENANT's live exception-plane readout (runtime/excprof,
        scoped like the xferstats counter families): cumulative exception
        rate, resolve-tier mix, the EWMA-vs-baseline drift score and the
        respecialize recommendation. Tenant-wide by design — drift is a
        property of the tenant's traffic distribution, not of one job."""
        from ..runtime import excprof

        return excprof.scope_report(self._rec.request.tenant)

    def attempts(self) -> list:
        """The retry ladder's audit trail: one record per FAILED attempt
        ({attempt, error, transient, action, backoff_s, t}). Empty for a
        job that succeeded first try."""
        return [dict(a) for a in self._rec.attempts]

    def latency_budget(self) -> dict:
        """The job's latency-budget vector (runtime/critpath): its
        end-to-end wall attributed into the canonical exclusive buckets
        (admission/queue waits, compile split, h2d/device/d2h, resolve
        tiers, merge, scheduler/other) with an honest ``unattributed``
        remainder, plus the swept critical path. Empty until the job is
        terminal or when critpath is disabled."""
        return dict(self._rec.latency_budget or {})

    # -- completion --------------------------------------------------------
    def wait(self, timeout: Optional[float] = None) -> str:
        """Block until the job reaches a terminal state (or `timeout`
        elapses); returns the state either way."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._svc._cond:
            while self._rec.state in (QUEUED, RUNNING):
                left = None if deadline is None \
                    else deadline - time.monotonic()
                if left is not None and left <= 0:
                    break
                self._svc._cond.wait(0.2 if left is None
                                     else min(0.2, left))
        return self._rec.state

    def result(self, timeout: Optional[float] = None):
        """The job's output rows (``collect=True`` requests). Raises
        JobFailed on failure, TimeoutError if still running at
        `timeout`."""
        state = self.wait(timeout)
        if state in (QUEUED, RUNNING):
            raise TimeoutError(f"job {self.id} still {state}")
        if state != DONE:
            raise JobFailed(
                f"job {self.id} {state}: {self._rec.error or 'unknown'}")
        return self._rec.result_rows


class JobRecord:
    """Service-internal per-job state (the handle wraps it)."""

    def __init__(self, request: JobRequest, weight: int):
        from ..api.metrics import Metrics

        self.id = uuid.uuid4().hex[:12]
        self.request = request
        self.state = QUEUED
        self.error: Optional[str] = None
        self.metrics = Metrics()
        # this job's metrics report ITS scoped counter family, never the
        # process-global registry (no cross-tenant bleed in responses)
        self.metrics.counters_source = self._counters
        self.exceptions: list = []
        self.result_rows: Optional[list] = None
        self.runner: Optional[_JobRunner] = None
        self.final_counters: Optional[dict] = None
        self.latency_budget: Optional[dict] = None   # runtime/critpath
                                            # bucket vector, stamped at
                                            # the terminal turn
        self.weight = max(1, int(weight))
        self.burst = 0                      # consecutive steps this round
        self.attempt = 0                    # completed FAILED attempts
        self.attempts: list = []            # one dict per failed attempt
                                            # (error, transient verdict,
                                            # backoff, action) — the retry
                                            # ladder's audit trail
        self.stats: dict = {"turns": 0, "finished_turn": None,
                            "queued_s": None, "wall_s": None,
                            "attempts": 0}
        self.t_submit = time.perf_counter()
        self.t_start: Optional[float] = None
        self.t_enqueue: Optional[float] = None   # last ready-queue append
                                                 # (stage-queue-wait metric)

    def _counters(self) -> dict:
        """The job's scoped xferstats family — live while running, the
        completion snapshot afterwards (the registry entry is released at
        the terminal turn)."""
        if self.final_counters is not None:
            return dict(self.final_counters)
        from ..runtime import xferstats

        return xferstats.scoped(self.id)

    def reset_for_retry(self) -> None:
        """Clear the per-ATTEMPT result state before a retry replays the
        job from stage 0: stage metrics, exception rows and result rows
        belong to the aborted attempt — keeping them would double-count
        them into the final response (the attempts audit trail and the
        scoped counter family deliberately persist across attempts)."""
        from ..api.metrics import Metrics

        self.metrics = Metrics()
        self.metrics.counters_source = self._counters
        self.exceptions = []
        self.result_rows = None


class _RunnerCtx:
    """Duck-typed context for source loading + stage execution inside the
    service (the exec/worker.py _Ctx pattern): options_store + backend is
    all the executors read."""

    def __init__(self, options_store, backend):
        self.options_store = options_store
        self.backend = backend
        self.recorder = None


class _JobRunner:
    """Executes one job stage-at-a-time. ``step()`` is the scheduler's
    fairness unit: one stage dispatch onto the warm device per call, so a
    long job's stage list interleaves with other tenants instead of
    monopolizing the chip."""

    def __init__(self, record: JobRecord, service_options,
                 default_budget: int):
        from ..core.options import ContextOptions
        from ..exec.local import LocalBackend

        req = record.request
        opts = ContextOptions(service_options.to_dict())
        if req.options:
            opts.update(req.options)
        # jobs are leaves of the service: no nested fan-out, no UI
        opts.set("tuplex.backend", "local")
        opts.set("tuplex.webui.enable", False)
        budget = req.memory_budget if req.memory_budget else default_budget
        if budget and budget > 0:
            # the per-job memory budget IS the backend MemoryManager
            # budget: partitions past it spill via the runtime/spill.py
            # LRU evictor (degrade to disk, never OOM the shared process)
            opts.set("tuplex.executorMemory", int(budget))
        self.record = record
        self.options = opts
        self.backend = LocalBackend(opts)
        self.ctx = _RunnerCtx(opts, self.backend)
        self.entries = list(req.stages)
        self.stages = [self._rebuild(e) for e in self.entries]
        if not self.stages:
            raise TuplexException("job has no stages")
        # re-specialization hot-swap (serve/respec): the record carries
        # the plan generation PINNED AT ADMISSION — applied here, at
        # every rebuild (retries included), so one job never mixes plan
        # generations and a promotion mid-flight only affects jobs
        # admitted after the swap
        ctrl = getattr(record, "_respec_ctrl", None)
        if ctrl is not None:
            ctrl.overlay_job(self)
        self.si = 0
        self.partitions: Any = []

    # ------------------------------------------------------------------
    def _rebuild(self, entry: dict):
        if "live" in entry:
            return entry["live"]
        from ..exec.serverless import rebuild_stage

        return rebuild_stage(entry["spec"], self.options,
                             files=entry.get("files"))

    def _load_input(self, entry: dict, stage):
        from ..api.dataset import _source_partitions

        indir = entry.get("indir")
        if indir:
            from ..io.tuplexfmt import TuplexFileSourceOperator

            src = TuplexFileSourceOperator(self.options, indir)
            return src.load_partitions(self.ctx)
        if getattr(stage, "source", None) is not None:
            # a list: the canary below reads the same inputs a second time
            return list(_source_partitions(self.ctx, stage, lazy=False))
        return self.partitions      # mid-pipeline: previous stage's output

    # ------------------------------------------------------------------
    def step(self) -> bool:
        """Run ONE stage; returns True when the job is complete."""
        from ..plan.physical import consumer_kind

        stage = self.stages[self.si]
        entry = self.entries[self.si]
        ctrl = getattr(self.record, "_respec_ctrl", None)
        if self.si == 0 or entry.get("indir") \
                or getattr(stage, "source", None) is not None:
            self.partitions = self._load_input(entry, stage)
            if self.si == 0:
                # whole-plan AOT prewarm on the shared compile pool —
                # admission-to-first-dispatch overlaps the compiles
                pre = getattr(self.backend, "precompile_plan", None)
                if pre is not None:
                    try:
                        pre(self.stages, self.partitions)
                    except Exception:
                        pass
                if ctrl is not None:
                    # aval hint for background candidate compiles: the
                    # stage-0 dispatch shapes, a few ShapeDtypeStructs —
                    # never a partition reference (that would pin memory)
                    try:
                        from ..compiler import stagefn as SF

                        first = self.partitions[0] \
                            if isinstance(self.partitions, list) \
                            and self.partitions else None
                        if first is not None:
                            ctrl.note_input(
                                self.record.request.tenant,
                                SF.partition_avals(
                                    first, self.backend.bucket_mode),
                                first.schema)
                    except Exception:   # hint is best-effort
                        pass
        consumer = consumer_kind(self.stages, self.si)
        canary_inputs = self.partitions \
            if ctrl is not None \
            and getattr(self.record, "respec_canary", None) is not None \
            else None
        res = self.backend.execute_any(stage, self.partitions, self.ctx,
                                       intermediate=consumer)
        if canary_inputs is not None:
            # canary: shadow-execute the candidate generation on a
            # bounded fraction of the SAME inputs; the job's results
            # below stay 100% incumbent (never mixed across generations)
            ctrl.canary_stage(self, self.si, stage, canary_inputs, res)
        self.partitions = res.partitions
        self.record.metrics.record_stage(res.metrics)
        self.record.exceptions.extend(res.exceptions)
        self.si += 1
        return self.si >= len(self.stages)

    def finalize(self) -> None:
        rec = self.record
        if rec.request.collect:
            from ..runtime.columns import partition_to_pylist

            rows: list = []
            for p in self.partitions or []:
                self.backend.touch_partition(p)
                rows.extend(partition_to_pylist(p))
            rec.result_rows = rows
        else:
            rec.result_rows = []
        # drop the columnar partitions (and their spill files, via the
        # weakref finalizers): the record retains only the materialized
        # rows — terminal records live for the retention window and must
        # not pin a second copy of every job's output
        self.partitions = []

    def mm_metrics(self) -> dict:
        return self.backend.mm.metrics()

    def cleanup(self) -> None:
        """Remove the request's staged input parts (one-shot by contract;
        a long-lived service must not accumulate dead scratch). Best
        effort — the job's outcome is already decided."""
        cleanup_request_scratch(self.entries)


def cleanup_request_scratch(entries) -> None:
    """rmtree every staged 'indir' of a request's stage entries (requests
    are one-shot: once rejected or finished, the staged parts are dead)."""
    import shutil

    for entry in entries or []:
        indir = entry.get("indir") if isinstance(entry, dict) else None
        if indir:
            shutil.rmtree(indir, ignore_errors=True)


# ---------------------------------------------------------------------------
# request construction
# ---------------------------------------------------------------------------

def request_from_dataset(dataset, name: str = "job",
                         tenant: str = "default",
                         memory_budget: Optional[int] = None,
                         weight: Optional[int] = None,
                         options: Optional[dict] = None,
                         scratch_dir: Optional[str] = None) -> JobRequest:
    """Plan a DataSet's chain and package it as a JobRequest.

    Transform stages serialize via exec/serverless.serialize_stage; a
    memory-source first stage has its partitions staged to `scratch_dir`
    as native-format parts (the worker staged-parts protocol), so the
    request pickles whole. Join/aggregate stages (driver-tier in the
    serverless analog) ride live — in-process submissions only.
    """
    import os

    from ..exec.serverless import NotShippable, serialize_stage
    from ..plan import logical as L
    from ..plan.physical import TransformStage, plan_stages

    context = dataset._context
    stages = plan_stages(dataset._op, context.options_store)
    scratch = scratch_dir or os.path.join(
        context.options_store.get_str("tuplex.scratchDir",
                                      "/tmp/tuplex_tpu"),
        "serve", uuid.uuid4().hex[:12])
    entries: list = []
    for si, st in enumerate(stages):
        if not isinstance(st, TransformStage) \
                or getattr(st, "fold_op", None) is not None:
            # join/aggregate tiers and fused-fold stages ride live (the
            # spec doesn't carry a fold — same gate as the serverless
            # fan_out); in-process submissions only
            entries.append({"live": st})
            continue
        try:
            spec = serialize_stage(st)
        except NotShippable as e:
            log.info("stage %d not spec-serializable (%s); riding live",
                     si, e)
            entries.append({"live": st})
            continue
        src = st.source
        if src is None:
            entries.append({"spec": spec})
        elif spec["source"] is None:
            # memory / directory input: stage the partitions to scratch
            # (reference: uploads to the S3 scratch dir before invoking)
            if isinstance(src, L.ParallelizeOperator) \
                    or hasattr(src, "load_partitions"):
                from ..api.dataset import _source_partitions
                from ..io.tuplexfmt import write_partitions_tuplex

                parts = _source_partitions(context, st, lazy=False)
                indir = os.path.join(scratch, f"in-{si:03d}")
                write_partitions_tuplex(indir, list(parts),
                                        backend=context.backend)
                entries.append({"spec": spec, "indir": indir})
            else:
                entries.append({"live": st})
        else:
            files = list(getattr(src, "files", []) or []) or None
            entries.append({"spec": spec, "files": files})
    return JobRequest(stages=entries, name=name, tenant=tenant,
                      memory_budget=memory_budget, weight=weight,
                      options=dict(options or {}))
