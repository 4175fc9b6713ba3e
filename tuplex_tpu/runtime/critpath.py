"""Latency-budget plane: critical-path attribution, SLOs, blame.

Four telemetry planes already record *what happened* to a job — spans
(runtime/tracing), latency histograms (runtime/telemetry), device time
(runtime/devprof) and exception tiers (runtime/excprof) — but none of
them *explains* a slow job. This module turns the span timeline into an
answer:

* **critical-path attribution** — :func:`analyze_events` sweeps a job's
  span stream and attributes every instant of its end-to-end wall to
  exactly ONE canonical bucket (:data:`BUCKETS`): admission wait, stage
  queue wait, the compile trace/lower/xla split, H2D, device, the two
  resolve tiers, D2H, merge, scheduler/other — plus an explicit
  ``unattributed`` remainder so coverage is honest. Concurrency is
  resolved by a fixed priority order (what the job was actually blocked
  on): device execution beats an overlapped pool compile (overlap IS
  the optimization — off the critical path by construction), while the
  narrow host-side passes (resolve tiers, transfers, merge) beat the
  broad wrappers that contain them. The sweep touches each timeline
  slice once, so orphaned or cross-thread spans can degrade coverage
  but can never double-count.
* **tenant SLO plane** — ``tuplex.serve.sloMs`` (global) and
  ``tuplex.serve.tenantSlos`` ("a:250,b:500") declare per-tenant
  latency objectives; :func:`record_job` folds each terminal job into
  per-tenant attainment counters and two burn-rate windows (fast =
  ``tuplex.serve.sloBurnWindowS``, slow = 5x), and the ``slo`` health
  check (runtime/telemetry) goes degraded on a burning fast window and
  unhealthy on a sustained (both-window) burn — the SRE multi-window
  burn-rate alert, in-process.
* **regression blame** — each tenant keeps an EWMA baseline budget
  vector (same fold as excprof's drift anchor: ``excprof.ewma_alpha``);
  a job whose wall exceeds the baseline by ``critpathSlowFactor`` is
  reported as *which bucket grew* (``serve:slow-job`` instant span, the
  dashboard budget panel, ``python -m tuplex_tpu whyslow``).

Kill switch: ``TUPLEX_CRITPATH=0`` — the disabled path allocates
nothing (same contract as devprof/excprof). Everything here is bounded:
at most ``_MAX_ENTRIES`` tenants / retained job budgets, window deques
are capped, and one analysis looks at at most ``_MAX_SPANS`` spans.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict, deque
from typing import Optional

__all__ = [
    "BUCKETS", "enable", "enabled", "configure", "apply_options",
    "analyze_events", "analyze_ring", "record_job", "job_budget",
    "tenants", "tenant_report", "drop_tenant", "burn_rates",
    "attainment", "slo_for", "clear",
]

# ---------------------------------------------------------------------------
# canonical buckets
# ---------------------------------------------------------------------------

#: the exclusive budget vector every surface shares (bench JSON keys,
#: /metrics labels, dashboard panel rows, whyslow table) — order is the
#: display order: wait planes, compile split, data/compute planes,
#: resolve tiers, the catch-all, and the honest remainder
BUCKETS = (
    "admission_wait", "queue_wait",
    "compile_trace", "compile_lower", "compile_xla",
    "h2d", "device",
    "resolve_general", "resolve_interpreter",
    "d2h", "merge",
    "scheduler_other", "unattributed",
)

#: span-name prefix -> bucket, FIRST match wins (specific before
#: catch-all). Unknown span names fall into scheduler_other: they are
#: still attributable work — only timeline gaps are "unattributed".
_SPAN_BUCKET = (
    ("compile:trace", "compile_trace"),
    ("compile:lower", "compile_lower"),
    ("compile:xla", "compile_xla"),
    ("compile:aot-load", "compile_xla"),   # artifact load = compile plane
    ("compile:queue-wait", "compile_wait"),  # caller BLOCKED on the pool
    ("compile:", "scheduler_other"),       # cache probes, bookkeeping
    ("h2d:", "h2d"),
    ("d2h:", "d2h"),
    ("resolve:general", "resolve_general"),
    ("resolve:interpreter", "resolve_interpreter"),
    ("partition:merge", "merge"),
    ("partition:collect", "d2h"),          # result materialization plane
    ("partition:dispatch", "device"),      # exclusive time = the launch
    # the job thread BLOCKED on the chip, at the head of a collect
    ("dispatch:device-wait", "device_wait"),
)

#: sweep priority per bucket — when spans overlap, the highest priority
#: owns the slice (= what the job was blocked on). Narrow host-side
#: passes beat the wrappers containing them; device execution beats an
#: overlapped background compile (pool-compile overlap is off the
#: critical path — that overlap existing is the win, not a cost).
#: ``compile_wait`` is the exception that keeps the exclusion honest:
#: the caller-side compile:queue-wait span exists only while the job
#: thread is BLOCKED on the pool, so it outranks device and folds into
#: compile_xla in the reported vector (analyze_events) — a cold inline
#: compile is blamed on the compile plane, an overlapped pre-compile
#: (no wait span on the job thread) still costs nothing. ``device_wait``
#: is its twin on the chip's side: the wait for a dispatch's outputs sits
#: inside the collect that needs them (a d2h-plane wrapper), it is the
#: chip the thread is blocked on, so it outranks everything and folds
#: into ``device``.
_PRIO = {
    "device_wait": 12,
    "resolve_interpreter": 11, "resolve_general": 10, "merge": 9,
    "d2h": 8, "h2d": 7, "compile_wait": 6, "device": 5,
    "compile_xla": 4, "compile_lower": 3, "compile_trace": 2,
    "scheduler_other": 1,
}
_PRIO_BUCKET = {p: b for b, p in _PRIO.items()}
_N_PRIO = max(_PRIO.values()) + 1


def _classify(name: str) -> str:
    for prefix, bucket in _SPAN_BUCKET:
        if name.startswith(prefix):
            return bucket
    return "scheduler_other"


# ---------------------------------------------------------------------------
# gate + knobs (devprof/excprof discipline)
# ---------------------------------------------------------------------------

def _env_disabled() -> bool:
    return os.environ.get("TUPLEX_CRITPATH", "").strip().lower() in (
        "0", "false", "off")


_enabled = not _env_disabled()

_half_life_s = 120.0      # tuplex.tpu.critpathHalfLifeS (baseline EWMA)
_slow_factor = 1.5        # tuplex.tpu.critpathSlowFactor (wall vs EWMA)
_slo_ms = 0.0             # tuplex.serve.sloMs (0 = no SLO declared)
_tenant_slos: dict = {}   # tuplex.serve.tenantSlos overrides
_burn_window_s = 60.0     # tuplex.serve.sloBurnWindowS (fast; slow = 5x)
_slo_target = 0.9         # tuplex.serve.sloTarget (attainment objective;
                          # error budget = 1 - target)
_min_base_jobs = 3        # baseline jobs before blame may fire
_MIN_SLOW_S = 0.05        # absolute slack under the factor test so
                          # microsecond jitter on tiny jobs never flags
_MAX_ENTRIES = 1024       # bound on tenants AND retained job budgets
_MAX_SPANS = 2048         # spans one analysis will look at
_PATH_CAP = 96            # critical-path segments kept per job
_WINDOW_CAP = 4096        # (t, ok) samples per tenant burn window
_EMPTY: dict = {}         # allocation-free disabled-path return


def enable(on: bool = True) -> None:
    global _enabled
    _enabled = bool(on) and not _env_disabled()


def enabled() -> bool:
    return _enabled


def parse_slos(s) -> dict:
    """"a:250,b:500" -> {"a": 250.0, "b": 500.0} (per-tenant SLO ms);
    malformed entries are skipped, dicts pass through coerced."""
    if isinstance(s, dict):
        out = {}
        for k, v in s.items():
            try:
                out[str(k)] = float(v)
            except (TypeError, ValueError):
                continue
        return out
    out = {}
    for part in (s or "").split(","):
        part = part.strip()
        if not part or ":" not in part:
            continue
        k, _, v = part.partition(":")
        try:
            out[k.strip()] = float(v)
        except ValueError:
            continue
    return out


def configure(half_life_s: Optional[float] = None,
              slow_factor: Optional[float] = None,
              slo_ms: Optional[float] = None,
              tenant_slos=None,
              burn_window_s: Optional[float] = None,
              slo_target: Optional[float] = None,
              min_base_jobs: Optional[int] = None) -> None:
    global _half_life_s, _slow_factor, _slo_ms, _tenant_slos
    global _burn_window_s, _slo_target, _min_base_jobs
    if half_life_s is not None and half_life_s > 0:
        _half_life_s = float(half_life_s)
    if slow_factor is not None and slow_factor > 1.0:
        _slow_factor = float(slow_factor)
    if slo_ms is not None and slo_ms >= 0:
        _slo_ms = float(slo_ms)
    if tenant_slos is not None:
        _tenant_slos = parse_slos(tenant_slos)
    if burn_window_s is not None and burn_window_s > 0:
        _burn_window_s = float(burn_window_s)
    if slo_target is not None and 0.0 < slo_target < 1.0:
        _slo_target = float(slo_target)
    if min_base_jobs is not None and min_base_jobs >= 1:
        _min_base_jobs = int(min_base_jobs)


def apply_options(options) -> None:
    """Wire the process gate + knobs from ContextOptions. Like
    devprof/excprof, ``tuplex.tpu.critpath`` turns attribution ON,
    never off — the only OFF switches are the env kill switch and an
    explicit ``enable(False)``."""
    if options.get_bool("tuplex.tpu.critpath", True):
        enable(True)
    slo_ms = options.get_float("tuplex.serve.sloMs", -1.0)
    configure(
        half_life_s=options.get_float("tuplex.tpu.critpathHalfLifeS", 0.0)
        or None,
        slow_factor=options.get_float("tuplex.tpu.critpathSlowFactor", 0.0)
        or None,
        slo_ms=slo_ms if slo_ms >= 0 else None,
        tenant_slos=options.get_str("tuplex.serve.tenantSlos", "") or None,
        burn_window_s=options.get_float("tuplex.serve.sloBurnWindowS", 0.0)
        or None,
        slo_target=options.get_float("tuplex.serve.sloTarget", 0.0) or None)
    if _enabled:
        _ensure_health()


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()

#: tenant -> {"baseline": {bucket: ewma_s}, "wall_ewma", "unattr_ewma",
#:            "t_last", "n_base", "jobs", "slo_ok", "slo_miss",
#:            "slow_jobs", "window": deque[(monotonic, ok)]}
_TEN: "OrderedDict[str, dict]" = OrderedDict()
#: job id -> {"budget": ..., "verdict": ...} (newest _MAX_ENTRIES)
_RECENT: "OrderedDict[str, dict]" = OrderedDict()

_health_registered = False
_HEALTH_OWNER = object()


def clear() -> None:
    global _health_registered
    with _LOCK:
        _TEN.clear()
        _RECENT.clear()
        _health_registered = False


def _tenant_locked(tenant: str) -> dict:
    t = _TEN.get(tenant)
    if t is None:
        while len(_TEN) >= _MAX_ENTRIES:
            _TEN.pop(next(iter(_TEN)))
        t = _TEN[tenant] = {
            "baseline": None, "wall_ewma": None, "unattr_ewma": 0.0,
            "t_last": time.monotonic(), "n_base": 0, "jobs": 0,
            "slo_ok": 0, "slo_miss": 0, "slow_jobs": 0,
            "window": deque(maxlen=_WINDOW_CAP)}
    return t


def tenants() -> list:
    with _LOCK:
        return list(_TEN)


def drop_tenant(tenant: str) -> None:
    """Release a retired tenant's baseline + SLO windows (the serve
    retention sweep calls this — a churning tenant population must not
    grow this registry forever)."""
    with _LOCK:
        _TEN.pop(tenant, None)


def slo_for(tenant: str) -> float:
    """Resolved SLO milliseconds for `tenant` (override, else global);
    0.0 = no SLO declared."""
    return float(_tenant_slos.get(tenant, _slo_ms))


# ---------------------------------------------------------------------------
# span-tree reconstruction + critical-path sweep
# ---------------------------------------------------------------------------

def _prepare(evts) -> tuple:
    """(spans, n_orphans, n_dropped): normalize the raw event dicts to
    (ts, end, prio, name) tuples and count structural damage — spans
    that CLAIM nesting (depth > 0) but have no containing span left in
    their thread (ring-buffer wrap or embed-cap truncation severed the
    tree), and cross-thread ``complete()`` spans that straddle their
    neighbors instead of nesting. Both degrade attribution to whatever
    coarse bars remain; the sweep itself makes double-counting
    impossible regardless."""
    spans = []
    for e in evts:
        try:
            dur = float(e.get("dur"))
            ts = float(e["ts"])
        except (KeyError, TypeError, ValueError):
            continue
        if dur <= 0 or dur != dur:        # instants carry no wall time
            continue
        spans.append((ts, ts + dur, e.get("tid", 0),
                      int(e.get("depth", 0) or 0), str(e.get("name", ""))))
    n_dropped = 0
    if len(spans) > _MAX_SPANS:
        n_dropped = len(spans) - _MAX_SPANS
        spans.sort(key=lambda s: s[0] - s[1])   # keep the longest
        spans = spans[:_MAX_SPANS]
    spans.sort(key=lambda s: (s[0], s[0] - s[1]))
    # pool threads run NOTHING but compile spans inside a job's window
    # (exec/compilequeue workers re-tag themselves into the submitter's
    # stream): a tid with any non-compile span is a job thread, and a
    # compile running there is inline — it blocks the job and must
    # outrank device in the sweep, unlike an overlapped pool compile
    pool_tids = {tid for _ts, _end, tid, _d, _n in spans}
    for _ts, _end, tid, _depth, name in spans:
        if not name.startswith("compile:"):
            pool_tids.discard(tid)
    n_orphans = 0
    eps = 1.0                             # µs slack for rounded embeds
    stacks: dict = {}
    for ts, end, tid, depth, _name in spans:
        stack = stacks.setdefault(tid, [])
        while stack and stack[-1][1] + eps < end:
            if stack[-1][1] > ts + eps:   # straddles instead of nesting:
                n_orphans += 1            # a cross-thread complete() span
                break
            stack.pop()
        if not stack and depth > 0:
            n_orphans += 1                # nested child, parent gone
        stack.append((ts, end))
    return spans, pool_tids, n_orphans, n_dropped


def _sweep(spans, t0: float, t1: float, pool_tids=frozenset()) -> tuple:
    """Priority sweep over [t0, t1]: every elementary timeline slice is
    owned by the highest-priority active bucket (or by ``unattributed``
    when nothing is active), so the per-bucket sums can never exceed
    the window and never count a slice twice. Compile spans on a JOB
    thread (tid not in `pool_tids`) are inline — they block the job, so
    their priority is boosted over device while their reported bucket
    keeps the trace/lower/xla split. Returns
    (bucket_us: dict, path: [[ts, dur, bucket, name], ...])."""
    inline_prio = _PRIO["compile_wait"]
    bounds = []
    for ts, end, tid, _depth, name in spans:
        s, e = max(ts, t0), min(end, t1)
        if e <= s:
            continue
        bucket = _classify(name)
        prio = _PRIO[bucket]
        if prio < inline_prio and bucket.startswith("compile_") \
                and tid not in pool_tids:
            prio = inline_prio
        bounds.append((s, 1, prio, bucket, name))
        bounds.append((e, 0, prio, bucket, name))
    bounds.sort(key=lambda b: (b[0], b[1]))
    counts = [0] * _N_PRIO
    active = [[] for _ in range(_N_PRIO)]   # (bucket, name) per level
    bucket_us: dict = {}
    path: list = []
    t_prev = t0
    i, n = 0, len(bounds)
    while i <= n:
        t_cur = bounds[i][0] if i < n else t1
        if t_cur > t_prev:
            win = 0
            for p in range(_N_PRIO - 1, 0, -1):
                if counts[p]:
                    win = p
                    break
            if win and active[win]:
                bucket, name = active[win][-1]
            elif win:
                bucket, name = _PRIO_BUCKET[win], ""
            else:
                bucket, name = "unattributed", ""
            dur = t_cur - t_prev
            bucket_us[bucket] = bucket_us.get(bucket, 0.0) + dur
            if path and path[-1][2] == bucket and path[-1][3] == name:
                path[-1][1] += dur        # merge adjacent same-owner
            else:
                path.append([t_prev, dur, bucket, name])
            t_prev = t_cur
        if i == n:
            break
        _t, is_start, prio, bucket, name = bounds[i]
        if is_start:
            counts[prio] += 1
            active[prio].append((bucket, name))
        else:
            counts[prio] -= 1
            try:
                active[prio].remove((bucket, name))
            except ValueError:
                pass
        i += 1
    return bucket_us, path


def analyze_events(evts, wall_s: Optional[float] = None,
                   queued_s: float = 0.0, stage_queue_s: float = 0.0,
                   t0_us: Optional[float] = None,
                   t1_us: Optional[float] = None) -> Optional[dict]:
    """Attribute one job's end-to-end wall into the canonical exclusive
    bucket vector. `evts` is the job's span stream (tracing event
    dicts or recorder-embedded slices); `queued_s`/`stage_queue_s` are
    the scheduler's admission / stage-requeue waits (they happen while
    no span is active, so they ride in as scalars); `t0_us`/`t1_us`
    bound the running window on the trace clock (``tracing.
    to_trace_us``) — when omitted the span extent stands in. Returns
    None when disabled; never raises on damaged input — orphans and
    wrapped rings degrade to coarse bars with ``unattributed``
    absorbing the gap."""
    if not _enabled:
        return None
    spans, pool_tids, n_orphans, n_dropped = _prepare(evts or [])
    if spans:
        lo = min(s[0] for s in spans)
        hi = max(s[1] for s in spans)
    else:
        lo = hi = 0.0
    t0 = lo if t0_us is None else float(t0_us)
    t1 = hi if t1_us is None else float(t1_us)
    if t1 < t0:
        t0, t1 = t1, t0
    bucket_us, path = _sweep(spans, t0, t1, pool_tids) \
        if spans else ({}, [])
    # blocked-on-the-compile-pool slices report as compile_xla: the wait
    # wraps the pool's whole trace/lower/xla run, so the aggregate
    # compile bucket is the honest attribution for the blocked caller
    # (and blocked-on-the-chip slices as device)
    for wait, plane in (("compile_wait", "compile_xla"),
                        ("device_wait", "device")):
        if wait in bucket_us:
            bucket_us[plane] = bucket_us.get(plane, 0.0) \
                + bucket_us.pop(wait)
            for p in path:
                if p[2] == wait:
                    p[2] = plane
    buckets = {b: 0.0 for b in BUCKETS}
    for b, us in bucket_us.items():
        if b != "unattributed":
            buckets[b] = us / 1e6
    queued_s = max(0.0, float(queued_s or 0.0))
    stage_queue_s = max(0.0, float(stage_queue_s or 0.0))
    buckets["admission_wait"] = queued_s
    buckets["queue_wait"] = stage_queue_s
    covered = sum(v for b, v in buckets.items() if b != "unattributed")
    if wall_s is None:
        wall_s = (t1 - t0) / 1e6 + queued_s + stage_queue_s
    wall_s = max(float(wall_s), covered)  # never report >100% coverage
    buckets["unattributed"] = max(0.0, wall_s - covered)
    attributed = {b: v for b, v in buckets.items()
                  if b != "unattributed" and v > 0}
    dominant = max(attributed, key=attributed.get) \
        if attributed else "unattributed"
    unattr_frac = buckets["unattributed"] / wall_s if wall_s > 0 else 0.0
    return {
        "wall_s": round(wall_s, 6),
        "buckets": {b: round(v, 6) for b, v in buckets.items()},
        "unattributed_frac": round(unattr_frac, 4),
        "coverage_frac": round(1.0 - unattr_frac, 4),
        "dominant": dominant,
        "n_spans": len(spans),
        "n_orphans": n_orphans,
        "n_dropped": n_dropped,
        "degraded": bool(n_orphans or n_dropped),
        "path": [[round(p[0], 1), round(p[1], 1), p[2], p[3]]
                 for p in path[:_PATH_CAP]],
    }


def analyze_ring(events=None) -> Optional[dict]:
    """Whole-process convenience for one-shot Context runs (bench.py,
    ``Metrics.as_dict``): attribute the most recent top-level ``job``
    span's window from the shared tracing ring. None when disabled or
    nothing was traced."""
    if not _enabled:
        return None
    from . import tracing

    evts = events if events is not None else tracing.events()
    if not evts:
        return None
    job = None
    for e in evts:
        if e.get("name") == "job" and e.get("dur"):
            if job is None or e["ts"] >= job["ts"]:
                job = e
    if job is None:
        return analyze_events(evts)
    t0, t1 = job["ts"], job["ts"] + job["dur"]
    window = [e for e in evts
              if e.get("ts") is not None and t0 <= e["ts"] <= t1]
    return analyze_events(window, wall_s=job["dur"] / 1e6,
                          t0_us=t0, t1_us=t1)


# ---------------------------------------------------------------------------
# per-tenant baselines, SLO attainment, burn rates
# ---------------------------------------------------------------------------

def record_job(tenant: str, job_id: str, budget: Optional[dict],
               failed: bool = False) -> dict:
    """Fold one terminal job's budget into its tenant's EWMA baseline
    and SLO windows; returns the blame verdict ``{slow, blame,
    delta_s, baseline_wall_s, slo_ms, slo_ok}``. A failed job counts
    against the SLO but never calibrates the baseline (its truncated
    budget would teach the baseline a lie)."""
    if not _enabled or not budget:
        return _EMPTY
    from . import excprof

    wall = float(budget.get("wall_s", 0.0))
    obs = budget.get("buckets") or {}
    unattr = float(budget.get("unattributed_frac", 0.0))
    slo_ms = slo_for(tenant)
    now = time.monotonic()
    with _LOCK:
        t = _tenant_locked(tenant)
        t["jobs"] += 1
        slo_ok = None
        if slo_ms > 0:
            slo_ok = (not failed) and wall * 1000.0 <= slo_ms
            t["slo_ok" if slo_ok else "slo_miss"] += 1
            t["window"].append((now, slo_ok))
        slow = False
        blame = None
        delta = 0.0
        base_wall = t["wall_ewma"]
        if not failed and base_wall is not None \
                and t["n_base"] >= _min_base_jobs \
                and wall > max(base_wall * _slow_factor,
                               base_wall + _MIN_SLOW_S):
            slow = True
            t["slow_jobs"] += 1
            base = t["baseline"] or {}
            deltas = {b: obs.get(b, 0.0) - base.get(b, 0.0)
                      for b in BUCKETS}
            blame = max(deltas, key=deltas.get)
            delta = deltas[blame]
        if not failed:
            alpha = excprof.ewma_alpha(max(0.0, now - t["t_last"]),
                                       _half_life_s)
            if t["baseline"] is None:
                t["baseline"] = dict(obs)
                t["wall_ewma"] = wall
                t["unattr_ewma"] = unattr
            else:
                for b in BUCKETS:
                    t["baseline"][b] = t["baseline"].get(b, 0.0) + alpha \
                        * (obs.get(b, 0.0) - t["baseline"].get(b, 0.0))
                t["wall_ewma"] += alpha * (wall - t["wall_ewma"])
                t["unattr_ewma"] += alpha * (unattr - t["unattr_ewma"])
            t["n_base"] += 1
            t["t_last"] = now
        verdict = {"slow": slow, "blame": blame,
                   "delta_s": round(delta, 6),
                   "baseline_wall_s": round(base_wall, 6)
                   if base_wall is not None else None,
                   "slo_ms": slo_ms, "slo_ok": slo_ok}
        while len(_RECENT) >= _MAX_ENTRIES:
            _RECENT.pop(next(iter(_RECENT)))
        _RECENT[job_id] = {"tenant": tenant, "budget": budget,
                           "verdict": verdict}
    return verdict


def job_budget(job_id: str) -> Optional[dict]:
    """The retained ``{tenant, budget, verdict}`` for a recent job id
    (newest ``_MAX_ENTRIES`` jobs)."""
    with _LOCK:
        rec = _RECENT.get(job_id)
        return dict(rec) if rec is not None else None


def _burn_locked(t: dict, now: float) -> dict:
    fast_w = _burn_window_s
    slow_w = 5.0 * _burn_window_s
    budget = max(1e-9, 1.0 - _slo_target)
    fast_n = fast_miss = slow_n = slow_miss = 0
    for ts, ok in t["window"]:
        age = now - ts
        if age <= slow_w:
            slow_n += 1
            slow_miss += 0 if ok else 1
            if age <= fast_w:
                fast_n += 1
                fast_miss += 0 if ok else 1
    fast = (fast_miss / fast_n / budget) if fast_n else 0.0
    slow = (slow_miss / slow_n / budget) if slow_n else 0.0
    return {"fast": round(fast, 4), "slow": round(slow, 4),
            "fast_jobs": fast_n, "fast_misses": fast_miss,
            "slow_jobs": slow_n, "slow_misses": slow_miss}


def burn_rates(tenant: str) -> dict:
    """Multi-window burn-rate readout for `tenant`: miss fraction per
    window over the error budget (1 - sloTarget). 1.0 = burning the
    budget exactly; >1 = on track to violate the objective."""
    now = time.monotonic()
    with _LOCK:
        t = _TEN.get(tenant)
        if t is None:
            return {"fast": 0.0, "slow": 0.0, "fast_jobs": 0,
                    "fast_misses": 0, "slow_jobs": 0, "slow_misses": 0}
        return _burn_locked(t, now)


def attainment(tenant: str) -> Optional[float]:
    """Cumulative SLO attainment fraction for `tenant`; None when no
    SLO applies or nothing finished yet."""
    with _LOCK:
        t = _TEN.get(tenant)
        if t is None:
            return None
        n = t["slo_ok"] + t["slo_miss"]
        return (t["slo_ok"] / n) if n else None


def tenant_report(tenant: str) -> dict:
    """Numeric snapshot for one tenant (bench JSON / /metrics /
    dashboard all read this shape): jobs, the EWMA baseline budget
    vector, SLO attainment + burn rates, slow-job count."""
    now = time.monotonic()
    with _LOCK:
        t = _TEN.get(tenant)
        if t is None:
            return {"jobs": 0, "baseline": {}, "wall_ewma_s": 0.0,
                    "unattributed_ewma": 0.0, "slow_jobs": 0,
                    "slo_ms": slo_for(tenant), "attainment": None,
                    "burn": {"fast": 0.0, "slow": 0.0, "fast_jobs": 0,
                             "fast_misses": 0, "slow_jobs": 0,
                             "slow_misses": 0}}
        n = t["slo_ok"] + t["slo_miss"]
        return {
            "jobs": t["jobs"],
            "baseline": {b: round(v, 6)
                         for b, v in (t["baseline"] or {}).items()},
            "wall_ewma_s": round(t["wall_ewma"], 6)
            if t["wall_ewma"] is not None else 0.0,
            "unattributed_ewma": round(t["unattr_ewma"], 4),
            "slow_jobs": t["slow_jobs"],
            "slo_ms": slo_for(tenant),
            "attainment": round(t["slo_ok"] / n, 4) if n else None,
            "burn": _burn_locked(t, now),
        }


# ---------------------------------------------------------------------------
# slo health check (runtime/telemetry state machine input)
# ---------------------------------------------------------------------------

def _health_check():
    from . import telemetry

    now = time.monotonic()
    worst = telemetry.OK
    detail = None
    with _LOCK:
        snap = [(name, _burn_locked(t, now)) for name, t in _TEN.items()
                if slo_for(name) > 0]
    for name, br in snap:
        if br["fast"] >= 1.0 and br["fast_misses"] >= 1:
            sustained = br["slow"] >= 1.0 and br["slow_misses"] >= 2
            state = telemetry.UNHEALTHY if sustained \
                else telemetry.DEGRADED
            d = (f"tenant '{name}' burning its SLO budget "
                 f"(fast {br['fast']:.1f}x"
                 + (f", slow {br['slow']:.1f}x" if sustained else "")
                 + f"; {br['fast_misses']}/{br['fast_jobs']} recent "
                 f"job(s) missed {slo_for(name):.0f}ms)")
            if state == telemetry.UNHEALTHY \
                    or worst == telemetry.OK:
                worst, detail = state, d
            if worst == telemetry.UNHEALTHY:
                break
    return (worst, detail)


def _ensure_health() -> None:
    """Register the ``slo`` health check once (idempotent across
    clear(): re-registration is keyed on the registry actually holding
    the check, not just our flag)."""
    global _health_registered
    from . import telemetry

    if not telemetry.enabled():
        return
    with _LOCK:
        if _health_registered \
                and "slo" in telemetry.registry()._checks:
            return
        _health_registered = True
    telemetry.register_health_check("slo", _health_check,
                                    owner=_HEALTH_OWNER)
