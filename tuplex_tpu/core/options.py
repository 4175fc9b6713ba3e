"""Flat key-value config system.

Keeps the reference's option names (reference: core/src/ContextOptions.cc:198-250
release defaults; python/tuplex/context.py:147-187 normalization) so pipelines
written against tuplex/tuplex configure this framework unchanged, and adds
`tuplex.tpu.*` keys for the device execution model.

Values are stored stringly (like the reference) with typed getters; inputs may
be nested dicts / kwargs / YAML files, all flattened to `tuplex.`-prefixed keys.
"""

from __future__ import annotations

import json
import os
from typing import Any, Mapping


def _size_to_bytes(s: str | int | float) -> int:
    if isinstance(s, (int, float)):
        return int(s)
    s = s.strip()
    units = {
        "KB": 1 << 10, "MB": 1 << 20, "GB": 1 << 30, "TB": 1 << 40,
        "K": 1 << 10, "M": 1 << 20, "G": 1 << 30, "T": 1 << 40, "B": 1,
    }
    for suffix in sorted(units, key=len, reverse=True):
        if s.upper().endswith(suffix):
            return int(float(s[: -len(suffix)]) * units[suffix])
    return int(float(s))


def _to_bool(v: Any) -> bool:
    if isinstance(v, bool):
        return v
    return str(v).strip().lower() in ("true", "1", "yes", "on")


#: defaults mirror the reference's release table where the key carries over
DEFAULTS: dict[str, str] = {
    "tuplex.backend": "local",                 # local | tpu | multihost
    "tuplex.executorCount": "auto",            # host worker threads for IO/decode
    "tuplex.executorMemory": "1GB",
    "tuplex.driverMemory": "1GB",
    "tuplex.partitionSize": "32MB",
    "tuplex.runTimeMemory": "128MB",
    "tuplex.inputSplitSize": "64MB",
    "tuplex.useLLVMOptimizer": "true",         # accepted, ignored (XLA optimizes)
    "tuplex.autoUpcast": "false",
    "tuplex.allowUndefinedBehavior": "false",
    "tuplex.scratchDir": "/tmp/tuplex_tpu",
    "tuplex.logDir": ".",
    "tuplex.normalcaseThreshold": "0.9",
    "tuplex.optimizer.nullValueOptimization": "true",
    "tuplex.optimizer.speculateBranches": "true",
    "tuplex.optimizer.filterPushdown": "true",
    "tuplex.optimizer.selectionPushdown": "true",
    "tuplex.optimizer.operatorReordering": "false",
    "tuplex.optimizer.mergeExceptionsInOrder": "true",
    "tuplex.optimizer.sharedObjectPropagation": "true",
    "tuplex.csv.selectionPushdown": "true",
    "tuplex.csv.maxDetectionMemory": "256KB",
    "tuplex.csv.maxDetectionRows": "1000",
    "tuplex.csv.separators": "[',', ';', '|', '\\t']",
    "tuplex.csv.quotechar": '"',
    "tuplex.csv.comments": "['#']",
    "tuplex.sample.maxDetectionRows": "1000",
    "tuplex.webui.enable": "false",
    "tuplex.webui.port": "5000",
    "tuplex.webui.url": "localhost",
    "tuplex.webui.exceptionDisplayLimit": "5",
    "tuplex.redirectToPythonLogging": "false",
    "tuplex.aws.scratchDir": "",
    "tuplex.aws.maxConcurrency": "100",
    "tuplex.aws.requestTimeout": "600",     # per-task seconds
    "tuplex.aws.retryCount": "2",           # re-invocations before degrade
    "tuplex.aws.workerPlatform": "cpu",     # jax platform inside workers
                                            # ("" = inherit; one local chip
                                            # cannot be shared by N procs)
    "tuplex.aws.reuseWorkers": "true",      # warm container reuse analog
    # --- job-service keys (serve/: multi-tenant pipelines, one warm device)
    "tuplex.serve.queueDepth": "64",        # max queued+running jobs; a
                                            # submit past this blocks for
                                            # admissionTimeoutS, then is
                                            # REJECTED (backpressure, never
                                            # an unbounded backlog)
    "tuplex.serve.admissionTimeoutS": "30", # seconds a submit may wait on a
                                            # full queue before rejection
    "tuplex.serve.slots": "1",              # scheduler worker slots = max
                                            # concurrent in-flight device
                                            # dispatches (1 on a single
                                            # chip: no job can monopolize
                                            # it, nothing oversubscribes it)
    "tuplex.serve.jobMemory": "256MB",      # default per-job memory budget:
                                            # each job's private
                                            # MemoryManager budget — beyond
                                            # it the job's partitions SPILL
                                            # (runtime/spill.py LRU) instead
                                            # of OOM-ing the shared process
    "tuplex.serve.maxJobMemory": "0",       # cap on a request's memory
                                            # budget; a request asking more
                                            # is rejected at admission with
                                            # a clear error (0 = uncapped)
    "tuplex.serve.retainJobs": "256",       # completed/failed job records
                                            # (incl. materialized result
                                            # rows) the service keeps for
                                            # late fetches; older terminal
                                            # records are dropped so a
                                            # long-lived service stays
                                            # bounded (held JobHandles keep
                                            # their own record alive)
    "tuplex.serve.retryCount": "2",         # job-level retry ladder: a job
                                            # whose failure classifies as
                                            # TRANSIENT (device/dispatch
                                            # runtime errors, compile
                                            # deadline, injected transient
                                            # faults) is requeued up to
                                            # this many times from stage 0;
                                            # deterministic failures (user
                                            # code, bad requests) short-
                                            # circuit with a clear error.
                                            # Every attempt lands in the
                                            # job record + tenant span
                                            # stream + the
                                            # serve_job_retries counter.
                                            # The wire loop reuses it as
                                            # the crash-requeue budget: a
                                            # job that was in flight when
                                            # the serve process died is
                                            # requeued on restart until
                                            # its requeue count exceeds
                                            # this, then failed cleanly
    "tuplex.serve.retryBackoffS": "0.5",    # base of the exponential
                                            # retry backoff: attempt k
                                            # waits retryBackoffS * 2^(k-1)
                                            # seconds before requeueing
                                            # (the slot is freed while it
                                            # waits; 0 = immediate)
    "tuplex.serve.tenantWeights": "",       # "tenantA:2,tenantB:1" —
                                            # deficit-weighted round-robin:
                                            # weight w = w consecutive stage
                                            # dispatches per scheduler cycle
                                            # (unlisted tenants weigh 1)
    "tuplex.serve.metricsPort": "-1",       # loopback HTTP port for
                                            # Prometheus /metrics +
                                            # /healthz on `python -m
                                            # tuplex_tpu serve` (runtime/
                                            # telemetry). -1 = no server;
                                            # 0 = pick a free port and
                                            # announce it in
                                            # <root>/metrics.port
    "tuplex.serve.metricsPromS": "5",       # seconds between atomic
                                            # <root>/metrics.prom text
                                            # drops by the serve loop (the
                                            # wire protocol's no-socket
                                            # telemetry leg; <=0 disables)
    "tuplex.serve.healthSaturation": "0.9", # admission-queue fill fraction
                                            # (open/queueDepth) at which
                                            # the health state degrades;
                                            # full + rejecting = unhealthy
    "tuplex.serve.healthWedgedCompileS": "300",  # oldest in-flight compile
                                            # age (s) before the health
                                            # state degrades (the wedged-
                                            # compile watchdog; 3x ->
                                            # unhealthy)
    "tuplex.serve.healthStarvationS": "120",  # ready jobs waiting with all
                                            # slots busy and no turn
                                            # finishing for this long ->
                                            # degraded (4x -> unhealthy)
    "tuplex.serve.driftWindowS": "10",      # exception-plane drift window
                                            # (runtime/excprof): observed
                                            # per-tenant exception traffic
                                            # folds into the EWMA profile
                                            # every this-many seconds; the
                                            # drift score compares the
                                            # EWMA against the tenant's
                                            # plan-time-anchored baseline
                                            # and trips
                                            # respecialize_recommended one
                                            # window after a distribution
                                            # shift
    "tuplex.serve.sloMs": "0",              # per-job latency objective
                                            # (milliseconds, end-to-end:
                                            # admission to terminal) every
                                            # tenant is held to by the
                                            # latency-budget plane
                                            # (runtime/critpath): each
                                            # finished job counts toward
                                            # its tenant's attainment and
                                            # burn-rate windows, and the
                                            # `slo` health check degrades
                                            # on a burning fast window.
                                            # 0 = no SLO declared
    "tuplex.serve.tenantSlos": "",          # "tenantA:250,tenantB:1000" —
                                            # per-tenant SLO overrides in
                                            # milliseconds (unlisted
                                            # tenants use sloMs)
    "tuplex.serve.sloBurnWindowS": "60",    # the FAST burn-rate window in
                                            # seconds (the slow window is
                                            # 5x): burn = window miss
                                            # fraction / error budget;
                                            # fast >= 1 -> degraded, fast
                                            # AND slow >= 1 (sustained)
                                            # -> unhealthy, recovery is
                                            # automatic as misses age out
    "tuplex.serve.sloTarget": "0.9",        # attainment objective the
                                            # burn rate is normalized
                                            # against (error budget =
                                            # 1 - target; 0.9 = 10% of
                                            # jobs may miss before burn
                                            # reads 1.0)
    "tuplex.serve.respec": "true",          # closed-loop self-healing
                                            # (serve/respec.py): when a
                                            # tenant's exception-plane
                                            # drift trips respecialize_
                                            # recommended (runtime/
                                            # excprof), re-speculate its
                                            # plan from the LIVE observed
                                            # code distribution, compile
                                            # the candidate on the
                                            # background compile lane,
                                            # canary it on the tenant's
                                            # next job, and hot-swap at a
                                            # job boundary (the incumbent
                                            # stays the fallback rung in
                                            # exec/local's tier-restart
                                            # ladder). false = sense only
                                            # (the PR-13 behavior)
    "tuplex.serve.respecCheckS": "1",       # seconds between controller
                                            # drift polls per tenant
    "tuplex.serve.respecDebounce": "2",     # consecutive polls a tenant
                                            # must stay respecialize-
                                            # recommended before a
                                            # candidate build starts (one
                                            # noisy window must not spend
                                            # a background compile)
    "tuplex.serve.respecCooldownS": "120",  # minimum seconds between
                                            # respecialization attempts
                                            # for one tenant (promote or
                                            # abandon both arm it)
    "tuplex.serve.respecCanaryFrac": "0.25",  # fraction of the canary
                                            # job's partitions shadow-
                                            # executed on the candidate
                                            # per stage (>=1 partition;
                                            # the job's OWN results always
                                            # come from the incumbent)
    "tuplex.serve.respecCompileDeadlineS": "120",  # ceiling on the whole
                                            # candidate compile phase; a
                                            # candidate that cannot
                                            # compile in time is
                                            # quarantined, never promoted
    "tuplex.serve.respecQuarantineS": "300",  # base cooldown after a
                                            # quarantined candidate; the
                                            # SAME candidate signature
                                            # (content-addressed
                                            # `.respecquar` marker)
                                            # doubles it per repeat so a
                                            # poisoned respec cannot flap
    # --- TPU-native keys ---------------------------------------------------
    "tuplex.tpu.deviceBatchSize": "1048576",    # rows per device dispatch
    "tuplex.tpu.padBucketing": "q8",            # q8 | pow2 | exact
    "tuplex.tpu.filterCompaction": "true",      # selection-vector compaction
    "tuplex.tpu.maxStrBytes": "4096",           # cap for fixed-width str cols
    "tuplex.tpu.meshShape": "auto",             # e.g. "8" or "4x2"
    "tuplex.tpu.meshAxes": "data",
    "tuplex.tpu.donateBuffers": "true",
    "tuplex.tpu.interpretOnly": "false",        # force interpreter (debugging)
    "tuplex.tpu.jitCacheSize": "128",
    "tuplex.tpu.profileDir": "",            # jax.profiler trace per action
    "tuplex.tpu.compileBudgetS": "480",     # ceiling on a stage's predicted
                                            # compile seconds: where the
                                            # platform has a compile-cost
                                            # curve (plan/splittuner.py:
                                            # XLA:CPU), the planner splits
                                            # the stage finer to stay under
    "tuplex.tpu.compileDeadlineS": "300",   # hard ceiling per stage
                                            # compile, DEFAULT ON: the
                                            # compile runs in a killable
                                            # forked child (exec/
                                            # compilequeue isolation_mode;
                                            # TUPLEX_COMPILE_ISOLATION=
                                            # thread reverts to the old
                                            # abandon-on-a-thread wait) and
                                            # a blown deadline SIGKILLs it,
                                            # writes a content-addressed
                                            # `.timeout` marker so later
                                            # processes skip the wedge
                                            # instantly, and degrades the
                                            # WHOLE stage to one slower
                                            # tier (host-CPU compile, else
                                            # interpreter — never a
                                            # mid-stage compiled/
                                            # interpreted row split).
                                            # 0 disables
    "tuplex.tpu.parallelCompile": "true",   # plan-level AOT compile pool
                                            # (exec/compilequeue.py);
                                            # TUPLEX_PARALLEL_COMPILE=0 also
                                            # disables
    "tuplex.tpu.staticTypes": "true",       # sample-free specialization
                                            # (compiler/typeinfer.py):
                                            # abstract-interpret UDF ASTs
                                            # and skip the CPython sample
                                            # trace when the output type is
                                            # exactly decidable. Default on;
                                            # TUPLEX_STATIC_TYPES=0 is the
                                            # env escape hatch (wins over
                                            # the option, for A/B timing)
    "tuplex.tpu.telemetry": "true",         # serve-layer telemetry
                                            # (runtime/telemetry.py):
                                            # streaming latency histograms,
                                            # sampled gauges, health checks
                                            # behind Metrics.
                                            # export_prometheus() and the
                                            # serve /metrics endpoint.
                                            # Default on (O(1) per record).
                                            # Like tuplex.tpu.trace the
                                            # gate is process-wide and the
                                            # option only ever turns it ON;
                                            # the TUPLEX_TELEMETRY=0 env
                                            # kill switch (wins over all)
                                            # makes every record a single
                                            # flag check, zero allocation
    "tuplex.tpu.devprof": "true",           # device-plane cost
                                            # attribution (runtime/
                                            # devprof.py): harvests XLA
                                            # cost/memory analysis per
                                            # compiled stage (persisted
                                            # next to the AOT artifact),
                                            # measures device time per
                                            # dispatch (launch→ready,
                                            # cold/warm split) and emits
                                            # roofline readouts into
                                            # stage metrics, bench JSON,
                                            # /metrics gauges, spans and
                                            # the dashboard. Default on.
                                            # The sample is taken where
                                            # the collect side waits for
                                            # a dispatch's outputs anyway
                                            # (exec/local._await_dispatch)
                                            # — exact where the host
                                            # waited, an upper bound
                                            # (`late`) where the outputs
                                            # were ready first — so the
                                            # schedule is the same on or
                                            # off. TUPLEX_DEVPROF=0 is
                                            # the env kill switch: it
                                            # turns the RECORDING off, a
                                            # single flag check (zero
                                            # allocation, test-pinned).
                                            # Like trace/telemetry the
                                            # gate is process-wide and
                                            # the option only turns it ON
    "tuplex.tpu.excprof": "true",           # exception-plane observability
                                            # (runtime/excprof.py): per-
                                            # stage x op x code windowed
                                            # accounting at the D2H unpack
                                            # + resolve-tier boundaries,
                                            # a plan-time baseline snapshot
                                            # (analyzer inventory + resolve
                                            # plan) with an EWMA drift
                                            # detector, the per-tenant
                                            # respecialize_recommended
                                            # signal and bounded sampled
                                            # deviant rows. Default on.
                                            # TUPLEX_EXCPROF=0 is the env
                                            # kill switch (wins over all):
                                            # every record path collapses
                                            # to one flag check, zero
                                            # allocation (test-pinned).
                                            # Like trace/telemetry/devprof
                                            # the gate is process-wide and
                                            # the option only turns it ON
    "tuplex.tpu.graphlint": "true",         # jaxpr-plane static analysis
                                            # (compiler/graphlint.py):
                                            # every stage jaxpr is vetted
                                            # BEFORE submission to XLA —
                                            # eqn census, static peak-
                                            # memory bound, dtype-creep /
                                            # broadcast-blowup lint, and
                                            # named compile-hazard rules
                                            # (the wide-str-compaction
                                            # XLA:CPU wedge). A wedge (or
                                            # a score past the threshold
                                            # below) pre-degrades at plan
                                            # time or vetoes at compile
                                            # time (CompileHazard rides
                                            # the normal tier ladder), so
                                            # pathological stages never
                                            # burn a deadline + SIGKILL.
                                            # Default on. TUPLEX_
                                            # GRAPHLINT=0 is the env kill
                                            # switch (wins over all):
                                            # every hook collapses to one
                                            # flag check, zero allocation
                                            # (test-pinned). Like devprof
                                            # the gate is process-wide
                                            # and the option only ever
                                            # turns it ON
    "tuplex.tpu.hazardThreshold": "60",     # hazard-score veto line in
                                            # predicted compile SECONDS
                                            # (graphlint's construct-
                                            # weighted census). 60 s sits
                                            # 2.6x above the worst clean
                                            # bundled stage (22.9 s), so
                                            # by default only a wedge-
                                            # severity finding crosses
                                            # it; <= 0 disables the score
                                            # veto (wedge rules still
                                            # veto). Also the per-segment
                                            # budget when a hazard score
                                            # forces a stage split
    "tuplex.tpu.excprofHalfLifeS": "30",    # EWMA half-life of the drift
                                            # detector: how fast the
                                            # observed exception profile
                                            # forgets old windows. Shorter
                                            # = trips faster on a shift
                                            # but noisier on bursty input
    "tuplex.tpu.excprofDriftThreshold": "0.5",  # drift_score (0..1) at
                                            # which respecialize_
                                            # recommended fires and the
                                            # exception_drift health check
                                            # reads degraded
    "tuplex.tpu.excprofSampleRows": "3",    # deviant rows captured per
                                            # stage x exception code
                                            # (first K, repr-truncated to
                                            # 160 chars) for the dashboard
                                            # "why did this row fall off
                                            # the fast path" panel. 0
                                            # disables capture entirely —
                                            # row payloads then never
                                            # leave the exec path
    "tuplex.tpu.excprofNormalRate": "0.05",  # exception-rate allowance
                                            # anchoring the drift baseline
                                            # for stages whose plan-time
                                            # inventory EXPECTS codes; a
                                            # code-free static verdict
                                            # gets a tight 0.005 floor
                                            # instead (any exception there
                                            # is evidence the speculation
                                            # went stale)
    "tuplex.tpu.critpath": "true",          # latency-budget plane
                                            # (runtime/critpath): per-job
                                            # critical-path attribution
                                            # over the span timeline into
                                            # the canonical exclusive
                                            # buckets (admission/queue
                                            # waits, compile trace/lower/
                                            # xla, h2d, device, resolve
                                            # tiers, d2h, merge,
                                            # scheduler/other,
                                            # unattributed), per-tenant
                                            # EWMA budget baselines with
                                            # slow-job blame, and the SLO
                                            # attainment/burn plane.
                                            # Surfaced via `python -m
                                            # tuplex_tpu whyslow`, the
                                            # dashboard budget panel,
                                            # tuplex_critpath_* /metrics
                                            # gauges and bench
                                            # latency_budget.* keys. Needs
                                            # tuplex.tpu.trace for full
                                            # coverage (without spans only
                                            # the wait buckets resolve).
                                            # TUPLEX_CRITPATH=0 kills it
                                            # with a zero-allocation
                                            # disabled path
    "tuplex.tpu.critpathHalfLifeS": "120",  # EWMA half-life of the per-
                                            # tenant baseline budget
                                            # vectors (the regression-
                                            # blame anchor; same fold as
                                            # excprof's drift EWMA)
    "tuplex.tpu.critpathSlowFactor": "1.5",  # a job whose end-to-end wall
                                            # exceeds its tenant's EWMA
                                            # baseline by this factor is
                                            # SLOW: the grown bucket is
                                            # blamed (serve:slow-job
                                            # instant + dashboard +
                                            # whyslow)
    "tuplex.tpu.trace": "false",            # structured span tracing
                                            # (runtime/tracing.py): nested
                                            # spans across plan/compile/
                                            # execute/merge, exported as
                                            # Chrome trace-event JSON via
                                            # Metrics.export_trace(path) /
                                            # `python -m tuplex_tpu trace`.
                                            # Off = zero overhead (no-op
                                            # spans). TUPLEX_TRACE=1 also
                                            # enables; TUPLEX_TRACE_BUFFER
                                            # sizes the ring (default 65536
                                            # spans)
}


class ContextOptions:
    def __init__(self, conf: Mapping[str, Any] | None = None, **kwargs: Any):
        self._store: dict[str, str] = dict(DEFAULTS)
        if conf:
            self.update(conf)
        if kwargs:
            self.update(kwargs)

    # -- updates ------------------------------------------------------------
    def update(self, conf: Mapping[str, Any] | str) -> None:
        if isinstance(conf, str):
            # YAML/JSON file path
            with open(conf) as fp:
                text = fp.read()
            try:
                data = json.loads(text)
            except json.JSONDecodeError:
                data = _parse_simple_yaml(text)
            self.update(data)
            return
        for k, v in _flatten(conf).items():
            self._store[_normalize_key(k)] = _stringify(v)

    def set(self, key: str, value: Any) -> None:
        self._store[_normalize_key(key)] = _stringify(value)

    def to_dict(self) -> dict[str, str]:
        """Flat copy for shipping to workers (serverless InvocationRequest
        carries the full option set, reference: Lambda.proto settings)."""
        return dict(self._store)

    # -- getters ------------------------------------------------------------
    def get(self, key: str, default: Any = None) -> Any:
        return self._store.get(_normalize_key(key), default)

    def get_str(self, key: str, default: str = "") -> str:
        return str(self.get(key, default))

    def get_bool(self, key: str, default: bool = False) -> bool:
        v = self.get(key)
        return default if v is None else _to_bool(v)

    def get_int(self, key: str, default: int = 0) -> int:
        v = self.get(key)
        if v is None:
            return default
        if isinstance(v, str) and v == "auto":
            return default
        return int(float(v))

    def get_float(self, key: str, default: float = 0.0) -> float:
        v = self.get(key)
        return default if v is None else float(v)

    def get_size(self, key: str, default: int = 0) -> int:
        v = self.get(key)
        return default if v is None else _size_to_bytes(v)

    def executor_count(self) -> int:
        v = self.get_str("tuplex.executorCount", "auto")
        if v == "auto":
            return max(1, (os.cpu_count() or 2) - 1)
        return int(v)

    def as_dict(self) -> dict[str, str]:
        return dict(self._store)

    def __contains__(self, key: str) -> bool:
        return _normalize_key(key) in self._store

    def __repr__(self) -> str:
        return f"ContextOptions({len(self._store)} keys)"


def _normalize_key(key: str) -> str:
    # reference: context.py:183-187 — keys are normalized to tuplex.*
    return key if key.startswith("tuplex.") else "tuplex." + key


def _stringify(v: Any) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    return str(v)


def _flatten(d: Mapping[str, Any], prefix: str = "") -> dict[str, Any]:
    out: dict[str, Any] = {}
    for k, v in d.items():
        key = f"{prefix}.{k}" if prefix else str(k)
        if isinstance(v, Mapping):
            out.update(_flatten(v, key))
        else:
            out[key] = v
    return out


def _parse_simple_yaml(text: str) -> dict[str, Any]:
    """Tiny `key: value` YAML subset (nested via indentation not supported —
    use dotted keys). Avoids a yaml dependency for config files."""
    out: dict[str, Any] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#") or ":" not in line:
            continue
        k, _, v = line.partition(":")
        out[k.strip()] = v.strip().strip("\"'")
    return out
