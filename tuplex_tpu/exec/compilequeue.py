"""Parallel + ahead-of-time stage compilation with content-addressed reuse.

The reference JITs a stage in milliseconds (TransformStage compile logged in
LocalBackend.cc:932-949; JobMetrics.h tracks compile seconds) because LLVM
codegen is local and cheap. Here a stage compile is an XLA compile — seconds
to minutes per stage and superlinear in graph size — so the compile pipeline
itself needs engineering:

  * **trace != compile.** Tracing a stage fn to a jaxpr is milliseconds and
    pure; compiling the lowering is the expensive part. Every entry point
    here traces eagerly (cheap, and the canonical jaxpr is the content
    address) and treats the COMPILE as the cacheable/parallelizable unit.
  * **content addressing.** The fingerprint is a hash over the canonical
    jaxpr text, the trace-hoisted constant VALUES, the input avals, the
    effective platform (incl. the host-ISA tag for XLA:CPU artifacts), the
    donation spec and caller salts (packing flag, mesh epoch). Two stages
    that lower to the same jaxpr — flights' isomorphic join-probe segments,
    re-planned pipelines in a fresh process — share one executable.
  * **three stores.** (1) an in-process dict fingerprint -> executable (the
    isomorphic-stage dedup), (2) an on-disk artifact cache of serialized
    PJRT executables (cross-process AOT reuse: run 2 of a pipeline
    deserializes instead of compiling), (3) an in-flight table so a pool
    worker and a foreground dispatch never compile the same fingerprint
    twice concurrently.
  * **a compile pool.** A small thread pool compiles all of a plan's
    stages concurrently and
    overlaps stage i+1's compile with stage i's execution (jax traces are
    thread-safe; XLA compiles release the GIL).

Everything is best-effort: any failure in the AOT machinery falls back to a
plain ``jax.jit`` so behavior (including NotCompilable propagation and the
local backend's trace-failure demotion ladder) is unchanged.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import queue
import threading
import time
from collections import OrderedDict
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeout
from typing import Any, Optional

import numpy as np

from ..runtime import tracing as TR
from ..runtime import xferstats

# -- counters ---------------------------------------------------------------
# stage_compiles: actual lowered.compile() invocations (the expensive event;
#   the cross-process acceptance test asserts this is ZERO on a warm cache)
# aot_hits/aot_misses: on-disk artifact lookups
# dedup_hits: in-process fingerprint hits (isomorphic stages, re-dispatch)
# compile_s: summed wall seconds spent inside lowered.compile()
STATS: dict[str, Any] = {
    "stage_compiles": 0, "compile_s": 0.0,
    "aot_hits": 0, "aot_misses": 0, "aot_errors": 0,
    "dedup_hits": 0, "pool_jobs": 0, "traces": 0,
    "deadline_timeouts": 0, "deadline_skips": 0,
    "subprocess_compiles": 0, "compiles_killed": 0,
    "fork_deadlocks": 0,
    "nodeser_marks": 0, "nodeser_skips": 0,
    "background_compiles": 0,
    # the compile plane at its cause (fault 1: speculative compiles the
    # next jobs do not find). prewarm_submitted = compiles queued by the
    # precompile driver / PackedStageFn.warm; prewarm_used = fingerprints
    # a prewarm looked up or built that a DISPATCH's lookup then took
    # (in-process store, in-flight join or disk store; once per
    # fingerprint). compile_starts is bumped as lowered.compile() begins
    # (in process or in a child); stage_compiles counts the ends that
    # succeeded, compile_failures the ones that raised, were killed or
    # handed nothing back — starts less both is what is still running.
    # prewarm_skipped = speculative submissions DROPPED: by the driver
    # on asking (the backend already traced that stage at those avals,
    # or the driver speculated it earlier in this process: nothing is
    # traced or queued), or by the pool after its trace (the fingerprint
    # is held or pending: no worker waits for it).
    "prewarm_submitted": 0, "prewarm_used": 0, "prewarm_skipped": 0,
    "compile_starts": 0, "compile_failures": 0,
    # pre-submission jaxpr vetting (compiler/graphlint): hazards_found =
    # fresh vetoes from a live analysis, hazards_avoided = every compile
    # the vet plane spared XLA (fresh vetoes + `.hazard` marker skips +
    # plan-time pre-degrades). compiles_killed staying at 0 while
    # hazards_avoided grows is the whole point: the wedge becomes a
    # prediction, not a survival story.
    "graphlint_ms": 0.0, "hazards_found": 0, "hazards_avoided": 0,
}

_LOCK = threading.Lock()
# fingerprint -> jax.stages.Compiled, LRU-bounded (TUPLEX_AOT_MEM_ENTRIES,
# default 256): an evicted executable's disk artifact remains, so a later
# request deserializes instead of recompiling — eviction costs a load, not
# a compile. Keeps a long-lived shell from pinning every executable the
# process ever built (the backend JitCache is bounded; this must be too).
_EXECS: "OrderedDict[str, Any]" = OrderedDict()
_EXEC_SALT: dict[str, str] = {}      # fingerprint -> caller salt (_EXECS keys)
_PENDING: dict[str, Future] = {}     # fingerprint -> in-flight compile
_PENDING_T: dict[str, float] = {}    # fingerprint -> compile start (monotonic)
_TAG: dict[str, list] = {}           # tag -> [seconds, count] (unconsumed)
_PREWARM_FPS: set = set()            # fingerprints a prewarm asked for
_PREWARM_USED: set = set()           # ... that a dispatch then took
_POOL: Optional["_DaemonPool"] = None
_BG_POOL: Optional["_DaemonPool"] = None   # low-priority background lane
_BG_TLS = threading.local()          # background_lane() thread flag


def _mem_capacity() -> int:
    try:
        return max(8, int(os.environ.get("TUPLEX_AOT_MEM_ENTRIES", "256")))
    except ValueError:
        return 256


class CompileTimeout(Exception):
    """A stage compile exceeded the compile deadline (or a previous run's
    marker says it did). In fork-isolation mode the compile CHILD was
    SIGKILLed — nothing keeps burning — and the caller degrades the
    WHOLE stage to one slower tier (host-CPU compile or interpreter,
    exec/local's tier ladder) instead of wedging the job on a
    pathological XLA compile (observed: a 3-op / 2.2k-eqn string stage
    that XLA:CPU chews >20 min and >120 GB on)."""


class CompileHazard(CompileTimeout):
    """Static vetting (compiler/graphlint) vetoed this stage's compile
    BEFORE submission: the jaxpr matches a wedge-severity rule (or
    scores past ``tuplex.tpu.hazardThreshold``), so handing it to XLA
    would predictably burn the deadline and a SIGKILL. Subclassing
    CompileTimeout is deliberate — the veto rides the exact same
    whole-stage tier ladder (host-CPU compile → interpreter) the killed
    compile would have landed on, minus the kill. Unlike a plain
    CompileTimeout it must propagate even with the deadline disabled:
    falling back to an unbounded plain jit would re-introduce the very
    hang the veto predicts."""


_TIMEOUTS: set = set()               # fingerprints that timed out (process)


class _AotUnsupported(Exception):
    """The AOT plumbing itself is unavailable (e.g. a jax without
    jit().trace()) — callers fall back to a plain jit; never raised for a
    genuine trace error, which must propagate like jit's would."""


class _DaemonPool:
    """Minimal thread pool on DAEMON threads. concurrent.futures'
    ThreadPoolExecutor joins its (non-daemon) workers at interpreter exit,
    so queued speculative stage compiles — up to minutes each —
    would block a finished process from exiting. Speculative work must
    never outlive the job that asked for it: daemon workers die with the
    process, and pending queue items are simply dropped."""

    def __init__(self, workers: int, name: str = "tpx-compile"):
        self._q: "queue.Queue" = queue.Queue()
        for i in range(workers):
            t = threading.Thread(target=self._run, daemon=True,
                                 name=f"{name}-{i}")
            t.start()

    def _run(self) -> None:
        while True:
            fut, fn, args, kwargs, stream, cause = self._q.get()
            if not fut.set_running_or_notify_cancel():
                continue
            # the submitter's span-stream tag (serve: the running job's
            # id) rides the queue item so compile/resolve-path spans
            # recorded on this pool thread stay tenant-tagged; workers
            # are reused, so the tag is always cleared afterwards. Its
            # span cause (TR.handoff) rides along the same way: a pool
            # compile's spans name the job that submitted it
            if stream is not None:
                TR.set_stream(stream)
            try:
                with TR.adopt(cause):
                    res = fn(*args, **kwargs)
                if isinstance(res, Future):
                    # the job found its work in someone else's hands (a
                    # speculative compile whose fingerprint is pending):
                    # its future follows theirs and this worker is free
                    res.add_done_callback(
                        lambda done, fut=fut: _follow(done, fut))
                else:
                    fut.set_result(res)
            except BaseException as e:  # noqa: BLE001 - future carries it
                fut.set_exception(e)
            finally:
                if stream is not None:
                    TR.set_stream(None)

    def submit(self, fn, *args, **kwargs) -> Future:
        fut: Future = Future()
        self._q.put((fut, fn, args, kwargs, TR.current_stream(),
                     TR.handoff()))
        return fut


def _follow(done: Future, fut: Future) -> None:
    """Settle `fut` as `done` was settled."""
    e = done.exception()
    if e is not None:
        fut.set_exception(e)
    else:
        fut.set_result(done.result())


def snapshot() -> dict:
    with _LOCK:
        return dict(STATS)


def delta(snap: dict) -> dict:
    with _LOCK:
        return {k: STATS[k] - snap.get(k, 0) for k in STATS}


def executable_devices() -> dict:
    """fingerprint -> {"devices": [(platform, device id)], "salt": caller
    salt} of every executable in the in-process store: where each would
    actually run. chip_smoke asserts that only the host-pinned ones (salt
    "/cpupin": the small-batch host resolve, exec/local._CpuJit) were
    built for the host CPU."""
    with _LOCK:
        execs = dict(_EXECS)
        salts = dict(_EXEC_SALT)
    return {fp: {"devices": [(d.platform, d.id) for d in _exec_devices(c)],
                 "salt": salts.get(fp, "")}
            for fp, c in execs.items()}


def pending_info() -> dict:
    """In-flight compile pressure for telemetry/health: how many
    fingerprints are being compiled right now and the age of the OLDEST
    one (seconds). A compile that wedges XLA keeps its entry until it
    finishes or its owner abandons it, so a growing oldest age is the
    wedged-compile watchdog signal the health state machine reads
    (runtime/telemetry)."""
    now = time.monotonic()
    with _LOCK:
        oldest = min(_PENDING_T.values(), default=None)
        queued = _POOL._q.qsize() if _POOL is not None else 0
        bg_queued = _BG_POOL._q.qsize() if _BG_POOL is not None else 0
        return {
            "inflight": len(_PENDING),
            "inflight_oldest_age_seconds":
                (now - oldest) if oldest is not None else 0.0,
            "pool_queued": queued,
            "background_queued": bg_queued,
        }


def consume_tag(tag: str) -> tuple[float, int]:
    """Take (and reset) the compile seconds + count attributed to `tag`
    since the last consume — the per-stage ``compile_s`` metric. Pool
    compiles submitted during an earlier stage's window but tagged for a
    later stage land on the later stage's record (attribution follows the
    executable's owner, not the wall-clock window it compiled in)."""
    with _LOCK:
        s, n = _TAG.pop(tag, (0.0, 0))
        return s, n


def clear() -> None:
    """Drop the in-process executable store + counters (tests). Disk
    artifacts stay unless the cache dir itself is removed."""
    with _LOCK:
        _EXECS.clear()
        _EXEC_SALT.clear()
        _TAG.clear()
        _PREWARM_FPS.clear()
        _PREWARM_USED.clear()
        _NODESER.clear()        # the on-disk .nodeser markers remain
        _DESER.clear()
        for k in STATS:
            STATS[k] = type(STATS[k])()


def pool() -> "_DaemonPool":
    global _POOL
    with _LOCK:
        if _POOL is None:
            _POOL = _DaemonPool(_workers())
        return _POOL


# ---------------------------------------------------------------------------
# the background compile lane (serve/respec candidate compiles)
# ---------------------------------------------------------------------------
# Speculative RE-specialization compiles must never slow a paying job:
# they ride a separate low-priority pool (one daemon worker by default,
# TUPLEX_BG_COMPILE_WORKERS) so a foreground dispatch never finds its
# compile-queue slot occupied by a background candidate, and the
# foreground pool's queue never has a candidate ahead of a job's stage.
# The lanes still SHARE the content-addressed stores and the in-flight
# table: a foreground request for a fingerprint the background lane is
# already compiling joins that future instead of compiling twice — the
# one way background work may interact with foreground, because it only
# ever makes the foreground FASTER.


class background_lane:
    """Context manager: ``submit_compile`` calls made by this thread
    while inside route to the background pool. The flag is thread-local
    and does not propagate into the pool job itself (nested submits from
    a bg worker would deadlock a one-worker lane)."""

    def __enter__(self):
        _BG_TLS.active = getattr(_BG_TLS, "active", 0) + 1
        return self

    def __exit__(self, *exc):
        _BG_TLS.active = max(0, getattr(_BG_TLS, "active", 1) - 1)
        return False


def background_active() -> bool:
    return bool(getattr(_BG_TLS, "active", 0))


def _bg_workers() -> int:
    try:
        return max(1, int(os.environ.get("TUPLEX_BG_COMPILE_WORKERS", "1")))
    except ValueError:
        return 1


def bg_pool() -> "_DaemonPool":
    global _BG_POOL
    with _LOCK:
        if _BG_POOL is None:
            _BG_POOL = _DaemonPool(_bg_workers(), name="tpx-bgcompile")
        return _BG_POOL


def _workers() -> int:
    try:
        return max(1, int(os.environ.get("TUPLEX_COMPILE_WORKERS", "4")))
    except ValueError:
        return 4


def parallel_compile_enabled() -> bool:
    """Pool gate (README: parallel-compile env toggle). XLA compiles
    release the GIL, so the default worker count (4) may exceed the core
    count harmlessly. TUPLEX_PARALLEL_COMPILE=0 disables."""
    return os.environ.get("TUPLEX_PARALLEL_COMPILE", "1") != "0"


# ---------------------------------------------------------------------------
# fingerprinting
# ---------------------------------------------------------------------------

def _platform_salt() -> str:
    from ..runtime.jaxcfg import aot_platform_tag

    return aot_platform_tag()


def fingerprint_traced(traced, salt: str = "") -> str:
    """Content address of a traced stage fn: canonical jaxpr text (variable
    names are already canonical in jaxpr pretty-printing) + the VALUES of
    trace-hoisted constants (two stages with identical structure but a
    different captured lookup table must not share an executable) + input
    avals + platform/ISA/x64 + caller salt (donation, packing, mesh epoch).
    """
    h = hashlib.sha256()
    cj = traced.jaxpr                      # ClosedJaxpr
    h.update(str(cj.jaxpr).encode())
    for c in cj.consts:
        a = np.asarray(c)                  # device consts: one host fetch
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    for aval in getattr(traced, "in_avals", ()) or ():
        h.update(repr(aval).encode())
    # the OUTPUT pytree structure is not in the jaxpr (flat outputs) but
    # IS part of the executable's contract: two fns computing the same
    # values under different output dict keys must not share — the stored
    # out_tree would replay the wrong keys (silently mis-labeled columns)
    import jax

    out_info = getattr(traced, "out_info", None)
    if out_info is None:
        raise _AotUnsupported("traced.out_info unavailable")
    h.update(repr(jax.tree_util.tree_structure(out_info)).encode())
    h.update(_platform_salt().encode())
    h.update(salt.encode())
    return h.hexdigest()


def fingerprint_fn(fn, args: tuple, donate_argnums=(), salt: str = "") -> str:
    """Fingerprint a python fn against abstract args (compilestats / the
    isomorphic-dedup report use this without compiling anything)."""
    import jax

    traced = jax.jit(fn, donate_argnums=tuple(donate_argnums)).trace(*args)
    return fingerprint_traced(traced, salt=salt + f"/don{tuple(donate_argnums)}")


# ---------------------------------------------------------------------------
# on-disk artifact store
# ---------------------------------------------------------------------------

_ARTIFACT_VERSION = 2       # v2: meta records the executable's devices


def _artifact_path(fp: str) -> Optional[str]:
    from ..runtime.jaxcfg import aot_cache_dir

    d = aot_cache_dir()
    if not d:
        return None
    return os.path.join(d, fp + ".aot")


# ---------------------------------------------------------------------------
# condemnation markers (one helper for every negative-cache verdict)
# ---------------------------------------------------------------------------
# A marker is a small JSON verdict file next to (or content-addressed
# like) an AOT artifact: `.timeout` (compile blew the deadline),
# `.nodeser` (serialized executable cannot deserialize/run), the
# serve plane's `.respecquar` (quarantined re-specialization candidate,
# serve/respec.py) and `.hazard` (static vetting vetoed the compile
# BEFORE submission — compiler/graphlint — so later processes skip the
# analysis AND the compile). The first three used to be ad-hoc bare
# files; the shared
# helper records PROVENANCE — which defect class condemned the artifact,
# on which platform, when and why — and ``read_marker`` only honors a
# marker whose recorded kind matches the suffix it was found under, so a
# healthy artifact can never be condemned by a different defect class
# (a torn write, a buggy writer, a copied file). Markers written by
# earlier builds (bare platform text) still count for their own suffix.

MARKER_KINDS = ("timeout", "nodeser", "respecquar", "hazard")


def marker_path(base_path: str, kind: str) -> str:
    return base_path + "." + kind


def write_marker(base_path: Optional[str], kind: str, reason: str = "",
                 **prov) -> Optional[str]:
    """Persist one condemnation verdict (atomic; best-effort by the
    negative-cache contract). Returns the marker path or None when there
    is nowhere to write (no cache dir)."""
    if base_path is None:
        return None
    import json

    rec = {"kind": kind, "platform": _platform_salt(),
           "created": time.time(), "reason": str(reason)[:400]}
    rec.update(prov)
    path = marker_path(base_path, kind)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(rec, f)
        os.replace(tmp, path)
        return path
    except OSError:   # pragma: no cover - marker is best-effort
        return None


def read_marker(base_path: Optional[str], kind: str) -> Optional[dict]:
    """The verdict at ``base_path + '.' + kind``, or None when absent OR
    when the file's recorded kind contradicts the suffix (a different
    defect class must never condemn this artifact through a mislabeled
    file)."""
    if base_path is None:
        return None
    import json

    path = marker_path(base_path, kind)
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        if not os.path.exists(path):
            return None
        # legacy marker (bare platform-salt text from earlier builds) or
        # torn write: the suffix it sits under still scopes it to ITS
        # kind, so it stands for that kind alone
        return {"kind": kind, "legacy": True}
    if not isinstance(rec, dict):
        return {"kind": kind, "legacy": True}
    if rec.get("kind") not in (None, kind):
        return None
    return rec


def _timeout_marker(fp: str):
    path = _artifact_path(fp)
    return None if path is None else path + ".timeout"


_NODESER: set = set()       # fingerprints with a known deserialize defect
_DESER: set = set()         # fps whose CURRENT _EXECS entry came from a
                            # deserialize (AOT disk hit / fork handback) —
                            # a fresh in-process compile discards the fp
                            # again. Provenance bound for the permanent
                            # .nodeser verdict: an async "Symbols not
                            # found" pins every live spec for safety, but
                            # only executables that actually rode the
                            # serialized-artifact path may durably mark
                            # their (possibly healthy) artifacts doomed


def _nodeser_marker(fp: str):
    path = _artifact_path(fp)
    return None if path is None else path + ".nodeser"


def _nodeser_known(fp: str) -> bool:
    """True when this fingerprint's serialized executable is known to be
    un-deserializable — it fails at LOAD, or loads but cannot RUN (both
    faces of the XLA:CPU "Symbols not found" gap) — in this process or,
    via the content-addressed on-disk marker, any earlier one. Cold runs
    then skip the doomed deserialize outright and compile in-process
    once, instead of paying load + failure + a recompile (the
    double-compile the ROADMAP residue names)."""
    if fp in _NODESER:
        return True
    return read_marker(_artifact_path(fp), "nodeser") is not None


def _note_nodeser(fp: str) -> None:
    """Record one fingerprint's deserialize defect: the in-process set
    plus the content-addressed on-disk ``.nodeser`` marker every later
    process consults before paying the doomed load."""
    with _LOCK:
        _NODESER.add(fp)
        STATS["nodeser_marks"] += 1
    write_marker(_artifact_path(fp), "nodeser",
                 reason="serialized executable cannot deserialize/run "
                        "(XLA 'Symbols not found' gap)", fp=fp)


def note_deserialize_defect(entry) -> None:
    """Persist the deserialize-defect verdict for the executable behind
    `entry` (the object AotJit/_CpuJit just watched fail with "Symbols
    not found"): drop it from the in-process store — later dedup hits
    would fail the same way — and write a ``.nodeser`` marker next to
    the artifact so every later process skips the load. The PERMANENT
    marker is provenance-bounded: only an entry that itself came off the
    serialized-artifact path may condemn its artifact — a fresh
    in-process compile swept up by a broad async pin
    (AotJit.note_async_defect covers every live spec) is dropped from
    the store but its perfectly good on-disk artifact stays loadable."""
    fps: list = []
    with _LOCK:
        for fp, c in list(_EXECS.items()):
            if c is entry:
                fps.append((fp, fp in _DESER))
                _EXECS.pop(fp, None)
    for fp, deserialized in fps:
        if deserialized:
            _note_nodeser(fp)


def _deadline_known_exceeded(fp: str) -> bool:
    """True when this fingerprint's compile already blew the deadline —
    in this process or (via the on-disk marker) any earlier one. A later
    SUCCESSFUL compile wins: the artifact is checked before the marker."""
    if fp in _TIMEOUTS:
        return True
    return read_marker(_artifact_path(fp), "timeout") is not None


def _note_deadline_exceeded(fp: str) -> None:
    _TIMEOUTS.add(fp)
    write_marker(_artifact_path(fp), "timeout",
                 reason="stage compile exceeded the deadline", fp=fp)


_HAZARDS: dict = {}          # fingerprint -> rule (this process)
_GL_TAG: dict = {}           # tag -> [lint_ms, hazards_found, hazards_avoided]


def _gl_tag_add(tag: str, ms: float = 0.0, found: int = 0,
                avoided: int = 0) -> None:
    with _LOCK:
        rec = _GL_TAG.setdefault(tag, [0.0, 0, 0])
        rec[0] += ms
        rec[1] += found
        rec[2] += avoided


def consume_graphlint(tag: str) -> tuple[float, int, int]:
    """Take (and reset) the static-vetting cost and hazard counts
    attributed to `tag` — the per-stage graphlint metrics, same
    attribution discipline as consume_tag()."""
    with _LOCK:
        ms, found, avoided = _GL_TAG.pop(tag, (0.0, 0, 0))
        return ms, found, avoided


def _graphlint_vet(traced, fp: str, tag: str, n_ops: int):
    """Pre-submission jaxpr vetting: runs compiler/graphlint over the
    REAL traced stage fn (the packed wrapper for packed dispatches —
    exactly what XLA would be handed) once per fingerprint. A wedge
    finding or a hazard score past ``tuplex.tpu.hazardThreshold`` writes
    the content-addressed ``.hazard`` marker and raises CompileHazard so
    the stage degrades tier-by-tier WITHOUT ever submitting the doomed
    compile. Returns the GraphReport (or None when the gate is off).
    Called only when no artifact exists — an executable that compiled
    fine before outranks any static verdict, same contract as the
    `.timeout` negative cache."""
    from ..compiler import graphlint as GL

    if not GL.enabled():
        return None
    rule = _HAZARDS.get(fp)
    rec = None
    if rule is None:
        rec = read_marker(_artifact_path(fp), "hazard")
        if rec is not None:
            rule = rec.get("rule", "hazard")
    if rule is not None:
        with _LOCK:
            STATS["hazards_avoided"] += 1
        _gl_tag_add(tag, avoided=1)
        TR.instant("compile:hazard-skip", "compile",
                   {"tag": tag[:16], "fp": fp[:12], "rule": rule})
        raise CompileHazard(
            f"stage jaxpr previously vetoed by static vetting "
            f"(rule {rule}, {fp[:12]}…)")
    import jax

    report = GL.analyze(traced.jaxpr, n_ops=max(n_ops, 1),
                        platform=jax.default_backend())
    if report is None:
        return None
    with _LOCK:
        STATS["graphlint_ms"] += report.elapsed_ms
    _gl_tag_add(tag, ms=report.elapsed_ms)
    threshold = GL.hazard_threshold()
    if report.wedge or (threshold > 0
                        and report.hazard_score > threshold):
        rule = next((f.rule for f in report.findings
                     if f.severity == "wedge"), "hazard-threshold")
        detail = "; ".join(f.line() for f in report.findings
                           if f.severity == "wedge") or (
            f"hazard score {report.hazard_score:.1f}s > "
            f"threshold {threshold:.0f}s")
        _HAZARDS[fp] = rule
        write_marker(_artifact_path(fp), "hazard", reason=detail, fp=fp,
                     rule=rule, score=float(min(report.hazard_score,
                                                1e9)),
                     n_eqns=report.n_eqns, n_ops=report.n_ops)
        with _LOCK:
            STATS["hazards_found"] += 1
            STATS["hazards_avoided"] += 1
        _gl_tag_add(tag, found=1, avoided=1)
        TR.instant("compile:hazard-veto", "compile",
                   {"tag": tag[:16], "fp": fp[:12], "rule": rule,
                    "n_eqns": report.n_eqns})
        raise CompileHazard(
            f"static vetting vetoed the stage compile ({rule}: {detail})")
    return report


def _exec_devices(compiled) -> list:
    """The devices an executable was compiled for (one for a stage
    executable, the mesh's for a sharded one)."""
    return list(compiled._executable.xla_executable.local_devices())


def _artifact_meta(compiled) -> dict:
    import jax

    devs = _exec_devices(compiled)
    return {"v": _ARTIFACT_VERSION, "platform": jax.default_backend(),
            "jax": jax.__version__, "created": time.time(),
            "exec_platform": devs[0].platform,
            "device_ids": [d.id for d in devs]}


def _load_devices(meta: dict):
    """This process's devices matching the ids the artifact was compiled
    for, or None when one is absent (a miss). jax's own default is EVERY
    device of the backend, which loads a one-device executable as an
    N-shard one ("Expected args to execute_sharded_on_local_devices to
    have N shards") wherever the process sees more than one device."""
    import jax

    ids = meta.get("device_ids")
    if not ids:
        return None
    by_id = {d.id: d for d in jax.devices(meta.get("exec_platform"))}
    if any(i not in by_id for i in ids):
        return None
    return [by_id[i] for i in ids]


def _disk_load(fp: str, path: Optional[str] = None):
    """Deserialize an AOT artifact onto the devices it was compiled for,
    or None. A mismatched platform/jax version or an absent device is a
    miss (prune_stale() reclaims such files). `path` overrides the
    content-addressed location (the subprocess-compile handback when no
    cache dir is configured)."""
    path = path if path is not None else _artifact_path(fp)
    if path is None or not os.path.exists(path):
        return None
    import jax
    from jax.experimental import serialize_executable as se

    with open(path, "rb") as f:
        rec = pickle.load(f)
    meta = rec.get("meta", {})
    if meta.get("v") != _ARTIFACT_VERSION \
            or meta.get("platform") != jax.default_backend() \
            or meta.get("jax") != jax.__version__:
        return None
    devices = _load_devices(meta)
    if devices is None:
        return None
    return se.deserialize_and_load(rec["payload"], rec["in_tree"],
                                   rec["out_tree"],
                                   backend=devices[0].client,
                                   execution_devices=devices)


def _disk_store(fp: str, compiled, path: Optional[str] = None) -> None:
    path = path if path is not None else _artifact_path(fp)
    if path is None:
        return
    from jax.experimental import serialize_executable as se

    payload, in_tree, out_tree = se.serialize(compiled)
    rec = {"meta": _artifact_meta(compiled), "payload": payload,
           "in_tree": in_tree, "out_tree": out_tree}
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        pickle.dump(rec, f)
    os.replace(tmp, path)                  # atomic vs concurrent writers


def prune_stale(cache_dir: Optional[str] = None) -> int:
    """Evict artifacts compiled for a different platform or jax version
    (a CPU artifact is useless — and on a different ISA dangerous — once
    the effective backend changes; fingerprints already partition them,
    this reclaims the disk). Returns the number of files removed."""
    import jax

    from ..runtime.jaxcfg import aot_cache_dir

    d = cache_dir or aot_cache_dir()
    if not d or not os.path.isdir(d):
        return 0
    removed = 0
    for name in os.listdir(d):
        if not name.endswith(".aot"):
            continue
        path = os.path.join(d, name)
        try:
            with open(path, "rb") as f:
                meta = pickle.load(f).get("meta", {})
            stale = meta.get("v") != _ARTIFACT_VERSION \
                or meta.get("platform") != jax.default_backend() \
                or meta.get("jax") != jax.__version__
        except Exception:
            stale = True                   # unreadable artifact: reclaim
        if stale:
            try:
                os.remove(path)
                removed += 1
            except OSError:
                pass
    return removed


# ---------------------------------------------------------------------------
# the compile core
# ---------------------------------------------------------------------------

def _compile_lowered(lowered):
    """The single expensive call — tests inject latency here to prove the
    pool actually runs compiles concurrently, and the fault harness
    (runtime/faults, TUPLEX_FAULTS="compile:...") injects hangs/raises
    here to prove a wedged compile is killed rather than waited out. In
    subprocess-isolation mode this body runs in the forked CHILD, so an
    injected hang is wedged exactly where a real XLA wedge would be."""
    from ..runtime import faults

    faults.maybe("compile")
    return lowered.compile()


# ---------------------------------------------------------------------------
# subprocess compile isolation
# ---------------------------------------------------------------------------
# A deadline is only honest if blowing it KILLS the work: abandoning a
# native XLA compile on a daemon thread leaves it burning CPU/RSS (the
# flights airport build-side wedge: >20 min, >120 GB on 3 ops) and can
# segfault interpreter teardown — which is why tuplex.tpu.compileDeadlineS
# shipped default-off for four PRs. Deadline-bearing compiles therefore
# run in a forked child: the parent traces and lowers (cheap, and the
# fingerprint needs the trace anyway), forks, and the child does the one
# expensive lowered.compile(), hands the executable back as a
# serialized-PJRT artifact through the content-addressed on-disk store,
# and _exits. A blown deadline SIGKILLs the child — the wedge dies WITH
# it — and the parent raises CompileTimeout into the normal whole-stage
# degrade ladder (exec/local: host-CPU compile or interpreter tier).
#
# Fork, not spawn: the lowered computation is not picklable (stage fns
# close over live plan state), while a forked child inherits it for
# free. The known risk — a lock held by another thread at fork time
# deadlocking the child — is covered by the same deadline that covers a
# real wedge: a deadlocked child is killed and the stage degrades.
# `auto` mode forks only on the CPU backend (forking a process that owns
# an accelerator client is undefined behavior in most PJRT plugins);
# accelerator backends keep the abandon-on-a-thread fallback.

_FORK_WARNED = False

# Forking while another thread sits inside native code (a jax trace or
# MLIR lower — both lock the shared MLIR context — an XLA compile, a
# PJRT executable (de)serialize) snapshots that thread's held C++ locks
# into the child, where no one will ever release them — the child
# deadlocks in lowered.compile() and burns its whole deadline before the
# kill (observed: a pool of 4 concurrent fork-compiles wedging one
# child on a futex). The gate serializes every fork() and every
# PARENT-side native phase of this module — trace, fingerprint (jaxpr
# pretty-print + const fetch), lower, artifact (de)serialize — so the
# fork snapshot is taken while compile-plane threads are only ever in
# Python-level waits. The forked CHILD inherits the gate in the held
# state and must never touch it (child code paths are gate-free).
# Residual risk (a non-compile thread inside native code at fork time,
# e.g. a serve dispatch executing a kernel) is covered by the deadline
# itself — the deadlocked child is killed and the stage degrades, which
# is the failure mode this layer exists to bound.
_FORK_GATE = threading.Lock()


def isolation_mode() -> str:
    """'fork' | 'thread' (TUPLEX_COMPILE_ISOLATION=auto|fork|thread;
    auto = fork on the CPU backend where os.fork exists)."""
    mode = os.environ.get("TUPLEX_COMPILE_ISOLATION", "auto").lower()
    if mode in ("thread", "0", "off", "none"):
        return "thread"
    if not hasattr(os, "fork"):
        return "thread"
    if mode == "fork":
        return "fork"
    try:
        import jax

        return "fork" if jax.default_backend() == "cpu" else "thread"
    except Exception:   # pragma: no cover - no jax backend yet
        return "thread"


# A forked child that snapshotted a foreign thread's held native lock
# deadlocks on a futex and STOPS accumulating cpu time (it may have
# burned a few seconds first — compiles can deadlock mid-flight); a
# genuinely wedged XLA compile (the thing the deadline exists for)
# burns cpu continuously for minutes. The distinction is readable from
# /proc/<pid>/stat, so the parent samples the child's cpu clock every
# second and kills a child that makes NO cpu progress for a whole grace
# window, then falls back to the in-thread compile — without writing a
# `.timeout` marker, because the compile itself was never the problem.
_DEADLOCK_GRACE_S = 5.0
_DEADLOCK_CPU_S = 0.2           # minimum cpu-seconds that count as
                                # progress between samples


def _child_cpu_s(pid: int):
    """The child's consumed cpu seconds (utime+stime), or None when
    /proc isn't available (non-Linux)."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(") ", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) \
            / os.sysconf("SC_CLK_TCK")
    except Exception:
        return None


def _kill_child(pid: int) -> None:
    import signal

    try:
        os.kill(pid, signal.SIGKILL)
    except OSError:     # already gone
        pass
    try:
        os.waitpid(pid, 0)          # reap — no zombie per killed compile
    except OSError:
        pass


def _compile_in_subprocess(fp: str, lowered, deadline_s: float):
    """Compile `lowered` in a killable forked child. Returns the compiled
    executable (deserialized from the artifact the child stored), None if
    the child failed for a non-deadline reason (caller falls back to the
    in-thread compile so the real error surfaces), or raises
    CompileTimeout after SIGKILLing a child that outlived the deadline."""
    path = _artifact_path(fp)
    ephemeral = None
    if path is None:                 # no cache dir: scratch handback file
        import tempfile

        ephemeral = os.path.join(
            tempfile.gettempdir(), f"tpx-aot-{os.getpid()}-{fp[:16]}.aot")
        path = ephemeral
    global _FORK_WARNED
    if not _FORK_WARNED:
        # jax warns on EVERY os.fork() from a threaded process; the
        # deadline is precisely the mitigation for the deadlock it warns
        # about (a deadlocked child is killed and the stage degrades), so
        # silence the repeat — once per process, message-scoped
        import warnings

        warnings.filterwarnings(
            "ignore", message=r".*os\.fork\(\) was called.*",
            category=RuntimeWarning)
        _FORK_WARNED = True
    with _FORK_GATE:
        t0 = time.perf_counter()   # deadline starts at the actual fork,
        pid = os.fork()            # not at the gate queue
    if pid == 0:
        # the child inherits _FORK_GATE in the HELD state (the parent
        # acquires it around fork()) — child code must never touch the
        # gate or any gated helper; _compile_lowered and the explicit-
        # path _disk_store below are gate-free by design
        code = 1
        try:
            # drop the inherited std fds FIRST: the child reports only
            # via its exit code, and an ORPHANED child (parent killed
            # mid-compile; a fork-deadlocked orphan can outlive it by
            # hours) holding the parent's stdout/stderr pipes keeps
            # every `cmd | consumer` harness waiting for EOF forever
            # (observed hanging a piped pytest run for 25 minutes)
            devnull = os.open(os.devnull, os.O_RDWR)
            for fd in (0, 1, 2):
                os.dup2(devnull, fd)
        except OSError:
            pass
        try:
            compiled = _compile_lowered(lowered)
            _disk_store(fp, compiled, path=path)
            code = 0
        except BaseException:        # noqa: BLE001 - child reports via rc
            code = 1
        finally:
            os._exit(code)           # no atexit/teardown in the child
    try:
        deadline = t0 + deadline_s if deadline_s and deadline_s > 0 \
            else None
        next_cpu_check = t0 + 1.0
        last_cpu = 0.0
        last_progress_t = t0
        while True:
            done, status = os.waitpid(pid, os.WNOHANG)
            if done:
                break
            now = time.perf_counter()
            if (deadline is None or now < deadline) \
                    and now >= next_cpu_check:
                next_cpu_check = now + 1.0
                cpu = _child_cpu_s(pid)
                if cpu is not None:
                    if cpu - last_cpu >= _DEADLOCK_CPU_S:
                        last_cpu = cpu
                        last_progress_t = now
                    elif now - last_progress_t >= _DEADLOCK_GRACE_S:
                        # cpu-stalled child = fork deadlock, not a
                        # wedge: kill it early and let the caller
                        # compile in-thread; no `.timeout` marker — the
                        # compile was never at fault
                        _kill_child(pid)
                        with _LOCK:
                            STATS["fork_deadlocks"] += 1
                        return None
            if deadline is not None and now >= deadline:
                _kill_child(pid)
                with _LOCK:
                    STATS["deadline_timeouts"] += 1
                    STATS["compiles_killed"] += 1
                _note_deadline_exceeded(fp)
                raise CompileTimeout(
                    f"stage compile exceeded the {deadline_s:g}s "
                    f"deadline ({fp[:12]}…); compile child killed")
            # fast compiles deserve a tight poll; long ones a cheap one
            time.sleep(min(0.05, max(0.002, (now - t0) / 20.0)))
        if not (os.WIFEXITED(status) and os.WEXITSTATUS(status) == 0):
            return None
        with _FORK_GATE:   # PJRT deserialize is native: see the gate
            try:
                return _disk_load(fp, path=path)
            except Exception as e:
                if not deserialize_defect(e):
                    raise
                # the child compiled fine but its serialized executable
                # cannot deserialize back into this parent (the XLA:CPU
                # "Symbols not found" gap at LOAD time). Persist the
                # `.nodeser` verdict — later calls and cold processes
                # then compile this fp in-process outright instead of
                # re-paying fork + doomed load — and return None: the
                # caller's in-thread fallback compiles inline, which is
                # deadline-safe (the finished child just proved this
                # compile terminates in time).
                _note_nodeser(fp)
                return None
    finally:
        if ephemeral is not None:
            try:
                os.remove(ephemeral)
            except OSError:
                pass


def _bump(name: str) -> None:
    with _LOCK:
        STATS[name] += 1


def _counted_compile(lowered):
    """`_compile_lowered` in this process between its two counters: a
    start, and a failure where it raises (the caller's `_note_compile`
    counts the end that succeeded)."""
    _bump("compile_starts")
    try:
        return _compile_lowered(lowered)
    except BaseException:
        _bump("compile_failures")
        raise


def _note_devprof(tag: str, fp: str, compiled) -> None:
    """Cost-attribution hook (runtime/devprof): harvest-or-recover XLA's
    cost/memory analysis for every executable that becomes visible here —
    fresh compiles, AOT disk hits, subprocess handbacks. Under the fork
    gate because cost_analysis()/memory_analysis() are native calls (see
    _FORK_GATE); best-effort by contract."""
    try:
        from ..runtime import devprof

        if devprof.enabled():
            with _FORK_GATE:
                devprof.note_compiled(tag, fp, compiled)
    except Exception:   # pragma: no cover - attribution is best-effort
        pass


def _note_compile(tag: str, dt: float) -> None:
    with _LOCK:
        STATS["stage_compiles"] += 1
        STATS["compile_s"] += dt
        rec = _TAG.setdefault(tag, [0.0, 0])
        rec[0] += dt
        rec[1] += 1
    xferstats.bump("stage_compiles", 1, tag=tag or None)


def default_deadline_s() -> float:
    """Hard ceiling on how long a dispatch will WAIT for one executable
    for callers that didn't pass one (tuplex.tpu.compileDeadlineS —
    default ON at 300 s — carries it down from the backend; env
    TUPLEX_COMPILE_DEADLINE_S for bare aot_jit users, default 0). The
    deadline became safe to default on once deadline-bearing compiles
    moved into a killable forked child (isolation_mode): a blown
    deadline SIGKILLs the compile instead of abandoning a native thread,
    and exec/local degrades the whole stage to ONE slower tier instead
    of splitting rows across compiled/interpreted mid-stage (the
    divergence that kept the old default off)."""
    try:
        return float(os.environ.get("TUPLEX_COMPILE_DEADLINE_S", "0"))
    except ValueError:
        return 0.0


def note_prewarm_skipped(n: int = 1) -> None:
    """`n` speculative submissions were dropped (STATS, above)."""
    if n:
        with _LOCK:
            STATS["prewarm_skipped"] += n


def _prewarm_owns(fp: str) -> None:
    """A prewarm came to OWN this fingerprint: it will load or compile
    it."""
    with _LOCK:
        _PREWARM_FPS.add(fp)


def _lookup_satisfied(fp: str, prewarm: bool) -> None:
    """A lookup was satisfied without a compile of its own (in-process
    store, in-flight join, disk store). Where it is a dispatch's and a
    prewarm owned the fingerprint, the prewarm was used: counted once."""
    if prewarm:
        return
    with _LOCK:
        if fp in _PREWARM_FPS and fp not in _PREWARM_USED:
            _PREWARM_USED.add(fp)
            STATS["prewarm_used"] += 1


def compile_traced(fn, args: tuple, donate_argnums=(), salt: str = "",
                   tag: str = "", n_ops: int = 0,
                   deadline_s: Optional[float] = None,
                   prewarm: bool = False):
    """Trace `fn` against `args` (avals or concrete arrays) and return a
    compiled executable for it, via — in order — the in-process fingerprint
    store, the on-disk AOT artifact cache, or an actual XLA compile (counted,
    timed, persisted to disk). `prewarm` marks a speculative
    call (the precompile driver's): see `_lookup_satisfied`. Such a call
    never waits for a compile in flight: it returns the pending Future
    itself, which the pool lets its own future follow.

    Trace-time exceptions (NotCompilable, emitter rejections) propagate to
    the caller exactly as they would from ``jax.jit(fn)(args)`` — the local
    backend's first-call demotion ladder depends on that.
    """
    import jax

    from ..runtime.jaxcfg import aot_cache_enabled

    if deadline_s is None:
        deadline_s = default_deadline_s()
    donate = tuple(donate_argnums)
    jfn = jax.jit(fn, donate_argnums=donate)
    trace_m = getattr(jfn, "trace", None)
    if trace_m is None:     # jax without the AOT .trace() entry point
        raise _AotUnsupported("jax.jit(...).trace unavailable")
    # errors OUT of the trace itself (NotCompilable, emitter rejections)
    # propagate exactly as they would from jax.jit(fn)(*args) — the local
    # backend's first-call demotion ladder depends on that
    with TR.span("compile:trace", "compile") as _sp:
        _sp.set("tag", tag[:16])
        with _FORK_GATE:   # traces take the shared MLIR/C++ context
            traced = trace_m(*args)   # locks a fork must not snapshot
    with _LOCK:
        STATS["traces"] += 1
    try:
        with _FORK_GATE:   # jaxpr pretty-print + const fetch: native too
            fp = fingerprint_traced(traced, salt=salt + f"/don{donate}")
    except Exception:
        # content addressing unavailable for this trace (e.g. a const
        # that can't be fetched/hashed): compile without caching — still
        # counted and timed, never a behavior change
        t0 = time.perf_counter()
        with TR.span("compile:xla", "compile") as _sp:
            _sp.set("tag", tag[:16]).set("n_ops", n_ops) \
               .set("cache", "unaddressable")
            with _FORK_GATE:               # native lower: see the gate
                lowered = traced.lower()
            compiled = _counted_compile(lowered)
        _note_compile(tag, time.perf_counter() - t0)
        _note_devprof(tag, "", compiled)   # tag-only: no content address
        return compiled

    while True:
        with _LOCK:
            cached = _EXECS.get(fp)
            if cached is not None:
                _EXECS.move_to_end(fp)
                STATS["dedup_hits"] += 1
                fut = None
            else:
                fut = _PENDING.get(fp)
                if fut is None:
                    fut = Future()
                    _PENDING[fp] = fut
                    _PENDING_T[fp] = time.monotonic()
                    break
        if prewarm and (cached is not None or fut is not None):
            note_prewarm_skipped()      # held or pending: nothing to do
            if cached is None:
                return fut              # only a dispatch blocks on it
        if cached is not None:
            _lookup_satisfied(fp, prewarm)
            xferstats.bump("cache_hits", 1, tag="dedup")
            TR.instant("compile:cache-hit", "compile",
                       {"tag": tag[:16], "cache": "hit",
                        "store": "in-process", "fp": fp[:12]})
            try:     # dedup hit: the cost record exists; only the
                from ..runtime import devprof   # tag->fp edge is new

                devprof.note_tag(tag, fp)
            except Exception:   # pragma: no cover
                pass
            return cached
        try:            # someone else is compiling this very fingerprint
            with TR.span("compile:queue-wait", "compile") as _sp:
                _sp.set("tag", tag[:16]).set("join", "in-flight") \
                   .set("fp", fp[:12])
                joined = fut.result(
                    timeout=deadline_s if deadline_s else None)
            _lookup_satisfied(fp, prewarm)
            try:    # the owner's _publish noted ITS tag; the joiner's
                from ..runtime import devprof   # tag->fp edge is new

                devprof.note_tag(tag, fp)
            except Exception:   # pragma: no cover
                pass
            return joined
        except FutureTimeout:
            raise CompileTimeout(
                f"waited {deadline_s:.0f}s on an in-flight compile "
                f"({fp[:12]}…)") from None
        except Exception:
            continue    # their attempt failed; try to own it ourselves

    if prewarm:
        _prewarm_owns(fp)

    def _publish(compiled):
        """Store a finished executable process-wide (+ disk happened in
        the job). Runs even when the waiting dispatch already gave up —
        a post-deadline completion still serves every later request."""
        with _LOCK:
            _EXECS[fp] = compiled
            _EXECS.move_to_end(fp)
            _EXEC_SALT[fp] = salt
            while len(_EXECS) > _mem_capacity():
                old, _ = _EXECS.popitem(last=False)  # disk artifact remains
                _EXEC_SALT.pop(old, None)
        # every executable that becomes dispatchable passes through here
        # (fresh compile, AOT disk hit, subprocess handback): the single
        # chokepoint where the cost-attribution layer sees it
        _note_devprof(tag, fp, compiled)
        return compiled

    def _compile_job():
        t0 = time.perf_counter()
        with TR.span("compile:lower", "compile") as _sp:
            _sp.set("tag", tag[:16])
            with _FORK_GATE:       # lowers are native code: see the gate
                lowered = traced.lower()
        with TR.span("compile:xla", "compile") as _sp:
            _sp.set("tag", tag[:16]).set("n_ops", n_ops) \
               .set("cache", "miss").set("fp", fp[:12])
            compiled = _counted_compile(lowered)
        _note_compile(tag, time.perf_counter() - t0)
        if aot_cache_enabled():
            try:
                with _FORK_GATE:   # native serialize: see the gate
                    _disk_store(fp, compiled)
            except Exception:   # pragma: no cover - disk best-effort
                with _LOCK:
                    STATS["aot_errors"] += 1
        with _LOCK:
            _DESER.discard(fp)      # current entry is an in-process build
        return _publish(compiled)

    try:
        compiled = None
        if aot_cache_enabled() and _nodeser_known(fp):
            # negative cache for the deserialize-defect gap: this
            # fingerprint's artifact loads but cannot run ("Symbols not
            # found") — skip the doomed deserialize and compile fresh
            # in-process, once, instead of load + call-fail + recompile
            with _LOCK:
                STATS["nodeser_skips"] += 1
            TR.instant("compile:nodeser-skip", "compile",
                       {"tag": tag[:16], "fp": fp[:12]})
        elif aot_cache_enabled():
            try:
                with TR.span("compile:aot-load", "compile") as _sp:
                    _sp.set("tag", tag[:16]).set("fp", fp[:12])
                    with _FORK_GATE:   # native deserialize: see the gate
                        compiled = _disk_load(fp)
                    _sp.set("cache",
                            "aot-hit" if compiled is not None else "miss")
                if compiled is not None:
                    with _LOCK:
                        _DESER.add(fp)
            except Exception as e:
                compiled = None
                with _LOCK:
                    STATS["aot_errors"] += 1
                if deserialize_defect(e):
                    # doomed load at the aot leg: persist the verdict so
                    # this is the LAST process that pays it
                    _note_nodeser(fp)
            with _LOCK:
                STATS["aot_hits" if compiled is not None
                      else "aot_misses"] += 1
            xferstats.bump("cache_hits" if compiled is not None
                           else "cache_misses", 1, tag="aot")
            if compiled is not None:
                _lookup_satisfied(fp, prewarm)
                _publish(compiled)
        if compiled is None and deadline_s and deadline_s > 0 \
                and _deadline_known_exceeded(fp):
            # negative cache: this fingerprint's compile blew the deadline
            # before (this process or an earlier one's on-disk marker) and
            # no artifact ever appeared — route to the interpreter NOW
            # instead of re-burning the deadline every process. Gated on
            # the deadline being ENABLED: a run with the default (off)
            # config must compile normally — a stale marker from an
            # opted-in run must not force the interpreter on runs that
            # never opted in, and a successful unbounded compile then
            # lands the artifact that overrides the marker for everyone.
            with _LOCK:
                STATS["deadline_skips"] += 1
            raise CompileTimeout(
                f"compile of {fp[:12]}… previously exceeded the deadline")
        if compiled is None:
            # pre-submission static vetting (compiler/graphlint): runs on
            # every jaxpr XLA has never successfully compiled (an existing
            # artifact or in-process hit never reaches here). A veto
            # raises CompileHazard — same tier ladder as a killed
            # compile, zero kills.
            _graphlint_vet(traced, fp, tag, n_ops)
        if compiled is None:
            if deadline_s and deadline_s > 0:
                # a known deserialize defect also rules out the FORK
                # path: its handback rides the same serialized-artifact
                # load that cannot work for this fp
                if isolation_mode() == "fork" and not _nodeser_known(fp):
                    # killable child: compile in a forked subprocess and
                    # hand the executable back through the on-disk
                    # artifact store; a blown deadline SIGKILLs the child
                    # (raising CompileTimeout from the helper) instead of
                    # abandoning a native thread
                    with TR.span("compile:lower", "compile") as _sp:
                        _sp.set("tag", tag[:16])
                        with _FORK_GATE:   # native lower: see the gate
                            lowered = traced.lower()
                    t0 = time.perf_counter()
                    with TR.span("compile:xla", "compile") as _sp:
                        _sp.set("tag", tag[:16]).set("n_ops", n_ops) \
                           .set("cache", "miss").set("fp", fp[:12]) \
                           .set("isolation", "subprocess")
                        _bump("compile_starts")   # in the child
                        try:
                            compiled = _compile_in_subprocess(
                                fp, lowered, deadline_s)
                        finally:
                            if compiled is None:   # killed, died, no handback
                                _bump("compile_failures")
                    if compiled is not None:
                        _note_compile(tag, time.perf_counter() - t0)
                        with _LOCK:
                            STATS["subprocess_compiles"] += 1
                            _DESER.add(fp)   # handback = deserialized
                        _publish(compiled)
                    # compiled None: the child died for a NON-deadline
                    # reason — fall through to the in-thread compile so
                    # the genuine error (an XLA rejection, a serializer
                    # gap) propagates exactly as it always did
                if compiled is None:
                    # abandon-on-a-thread fallback (no fork / accelerator
                    # backend / child failure): dedicated daemon thread
                    # (NOT the pool: a pool worker waiting on a nested
                    # pool job can deadlock the pool). A wedged compile
                    # keeps burning in background and publishes if it
                    # ever finishes, but the job moves on at the deadline
                    cfut: Future = Future()
                    cause = TR.handoff()    # the compile names its caller

                    def _runner():
                        try:
                            with TR.adopt(cause):
                                cfut.set_result(_compile_job())
                        except BaseException as e:  # noqa: BLE001
                            cfut.set_exception(e)

                    threading.Thread(target=_runner, daemon=True,
                                     name="tpx-compile-deadline").start()
                    try:
                        compiled = cfut.result(timeout=deadline_s)
                    except FutureTimeout:
                        _note_deadline_exceeded(fp)
                        with _LOCK:
                            STATS["deadline_timeouts"] += 1
                        raise CompileTimeout(
                            f"stage compile exceeded the "
                            f"{deadline_s:.0f}s deadline ({fp[:12]}…); "
                            f"falling back") from None
            else:
                compiled = _compile_job()
        with _LOCK:
            _PENDING.pop(fp, None)
            _PENDING_T.pop(fp, None)
        fut.set_result(compiled)
        return compiled
    except BaseException as e:
        with _LOCK:
            _PENDING.pop(fp, None)
            _PENDING_T.pop(fp, None)
        fut.set_exception(e)
        raise


def submit_compile(fn, args: tuple, donate_argnums=(), salt: str = "",
                   tag: str = "", n_ops: int = 0,
                   deadline_s=None, prewarm: bool = False) -> Future:
    """Queue a compile on the pool (ahead-of-time / overlapped with
    execution). Foreground dispatches of the same fingerprint join the
    in-flight future instead of compiling again. Inside a
    ``background_lane()`` the compile lands on the separate low-priority
    background pool instead — candidate re-specialization compiles never
    occupy a foreground slot or queue ahead of a job's stage compile."""
    bg = background_active()
    with _LOCK:
        STATS["pool_jobs"] += 1
        if bg:
            STATS["background_compiles"] += 1
        if prewarm:
            STATS["prewarm_submitted"] += 1
    target = bg_pool() if bg else pool()
    if not TR.enabled():
        return target.submit(compile_traced, fn, args,
                             donate_argnums=donate_argnums, salt=salt,
                             tag=tag, n_ops=n_ops, deadline_s=deadline_s,
                             prewarm=prewarm)

    t_sub = TR.now_us()

    def _pool_job():
        # the wait between submit and a worker picking the job up IS the
        # pool's queue pressure — record it as a real interval so a plan
        # whose compiles serialize behind each other shows the backlog
        TR.complete("compile:pool-queue-wait", "compile", t_sub,
                    TR.now_us() - t_sub,
                    {"tag": tag[:16], "lane": "bg" if bg else "fg"})
        return compile_traced(fn, args, donate_argnums=donate_argnums,
                              salt=salt, tag=tag, n_ops=n_ops,
                              deadline_s=deadline_s, prewarm=prewarm)

    return target.submit(_pool_job)


# ---------------------------------------------------------------------------
# the jit-compatible wrapper
# ---------------------------------------------------------------------------

def _mesh_sharding(x):
    """The sharding of a committed multi-device array (a mesh-staged
    batch), else None: only those pin the executable's devices — numpy
    and single-device arrays compile for the default device as ever."""
    sh = getattr(x, "sharding", None)
    if sh is not None and len(sh.device_set) > 1:
        return sh
    return None


def _leaf_aval(x):
    import jax

    return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                sharding=_mesh_sharding(x))


def _args_avals(args: tuple):
    """Abstract (ShapeDtypeStruct) mirror of concrete call args, or None
    when a leaf has no array protocol (python scalar etc.) — such calls
    use the plain-jit fallback, whose weak-type semantics differ."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    if any(not hasattr(l, "dtype") for l in leaves):
        return None, None
    avals = jax.tree_util.tree_unflatten(
        treedef, [_leaf_aval(l) for l in leaves])
    key = (treedef, tuple((np.shape(l), str(l.dtype), _mesh_sharding(l))
                          for l in leaves))
    return avals, key


_FALLBACK = object()


def deserialize_defect(e: BaseException) -> bool:
    """A deserialized PJRT executable that LOADED but cannot RUN — the
    known XLA:CPU gap where serialized executables of some fused kernels
    lose their jit-compiled symbol library ("Symbols not found: ...").
    Callers pin the affected spec to a plain in-process jit: correct,
    compiled, and — when the artifact came from the fork-isolation
    handback — safe to compile inline, because the killed-or-finished
    child already proved this compile terminates within the deadline."""
    return "Symbols not found" in str(e)


class AotJit:
    """Drop-in for ``jax.jit(fn)`` that routes per-input-spec compilation
    through the content-addressed store: dispatch never compiles an
    executable another stage (or another process) already built. Falls back
    to a plain jit on any AOT-machinery failure."""

    def __init__(self, fn, donate: bool = False, salt: str = "",
                 tag: str = "", n_ops: int = 0, deadline=None):
        self._fn = fn
        self._donate = (0,) if donate else ()
        self._salt = salt
        self._tag = tag
        self._n_ops = n_ops
        self._deadline = deadline
        self._by_spec: dict = {}
        self._jit = None
        self._last = None        # the executable the last call launched
        self._modnames: dict = {}

    @property
    def last_module(self) -> str:
        """HLO module name of the executable the last call launched — as
        the profiler's `XLA Modules` line reads it. De-duplication may
        have handed this fn an executable built under another stage's
        name; the plain-jit fallback launches this fn's own."""
        entry = self._last
        if entry is None:
            return "jit_" + getattr(self._fn, "__name__", "")
        name = self._modnames.get(id(entry))
        if name is None:
            try:
                name = entry.runtime_executable().hlo_modules()[0].name
            except Exception:
                name = "jit_" + getattr(self._fn, "__name__", "")
            self._modnames[id(entry)] = name
        return name

    def warm(self, *avals) -> Future:
        """Queue this fn's compile for `avals` on the pool, speculatively,
        under the very arguments a call would compile it with."""
        return submit_compile(
            self._fn, avals, donate_argnums=self._donate, salt=self._salt,
            tag=self._tag, n_ops=self._n_ops, deadline_s=self._deadline,
            prewarm=True)

    def _plain(self):
        if self._jit is None:
            import jax

            self._jit = jax.jit(self._fn, donate_argnums=self._donate)
        return self._jit

    def __call__(self, *args):
        entry = None
        key = None
        try:
            avals, key = self._args_key(args)
        except Exception:
            avals = None
        if avals is not None:
            entry = self._by_spec.get(key)
            if entry is None:
                # trace-time errors must escape like jit's would; only the
                # compile/AOT plumbing itself may fall back
                try:
                    entry = compile_traced(
                        self._fn, avals, donate_argnums=self._donate,
                        salt=self._salt, tag=self._tag, n_ops=self._n_ops,
                        deadline_s=self._deadline)
                except _AotUnsupported:
                    entry = None
                self._by_spec[key] = entry if entry is not None else _FALLBACK
        if entry in (None, _FALLBACK):
            self._last = None
            return self._plain()(*args)
        self._last = entry
        try:
            return entry(*args)
        except TypeError:
            # call-convention mismatch (aval/weak-type drift): pin this
            # spec to the plain jit, which retraces with jit's own rules
            self._by_spec[key] = _FALLBACK
            return self._plain()(*args)
        except Exception as e:
            if not deserialize_defect(e):
                raise
            # unloadable serialized executable (see deserialize_defect):
            # recompile this spec in-process via the plain jit instead of
            # demoting the stage to the interpreter; persist the verdict
            # so cold runs skip the doomed load (the `.nodeser` marker)
            note_deserialize_defect(entry)
            self._by_spec[key] = _FALLBACK
            return self._plain()(*args)

    def _args_key(self, args):
        avals, key = _args_avals(args)
        return avals, key

    def note_async_defect(self) -> bool:
        """The deserialize defect surfaced AFTER dispatch returned: jax
        dispatch is async, so a handback executable that loads-but-
        cannot-run may only fail when its device work actually executes
        — at the collect/block site, outside ``__call__``'s handler.
        Pin every live AOT entry to the plain in-process jit and persist
        their ``.nodeser`` verdicts. Returns True when something was
        pinned (the caller retries its dispatch once; a second failure
        finds nothing left to pin and degrades normally)."""
        hit = False
        for key, entry in list(self._by_spec.items()):
            if entry is not None and entry is not _FALLBACK:
                note_deserialize_defect(entry)
                self._by_spec[key] = _FALLBACK
                hit = True
        return hit


def aot_jit(fn, donate: bool = False, salt: str = "", tag: str = "",
            n_ops: int = 0, deadline=None):
    """The AOT-routed drop-in for ``jax.jit(fn)``; cached by the backend's
    JitCache exactly like a jit. Always the wrapper — disabling the disk
    cache (TUPLEX_AOT_CACHE=0) or the pool only turns those legs off,
    while compile counting, the in-process dedup store and the opt-in
    deadline keep working. TUPLEX_AOT_JIT=0 is the debugging escape hatch
    back to a bare jit (which silently drops all of the above)."""
    if os.environ.get("TUPLEX_AOT_JIT", "1") == "0":
        import jax

        return jax.jit(fn, donate_argnums=(0,) if donate else ())
    return AotJit(fn, donate=donate, salt=salt, tag=tag, n_ops=n_ops,
                  deadline=deadline)
