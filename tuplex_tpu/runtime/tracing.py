"""Process-wide structured tracing: nested, thread-aware spans.

The reference ships a live history server because dual-mode pipelines fail
in TIME, not just in counts — a job that "works" may be losing its wall
clock to compile-queue waits, D2H materialization, or the interpreter
resolve tier. Per-stage sums (api/metrics.py) can't show that; this module
records WHERE the seconds went as a span timeline:

  * ``span(name, cat)`` is a context manager that records one closed
    interval per entered span. Spans nest naturally — a per-thread stack
    tracks depth, and concurrent threads (the compile pool, source
    prefetch) interleave without locks on the hot path.
  * every record names its CAUSE: ``id`` (process-wide serial),
    ``parent`` (the enclosing span on that thread, else the span that
    handed the work over) and ``job`` (id of the ``job`` span at the
    root; None outside a job). Work crosses threads through one pair:
    ``handoff()`` on the submitting thread, ``with adopt(h):`` on the
    worker — a pool compile is put down to the job that submitted it.
  * ``open_spans()`` snapshots the spans open NOW on every thread (a
    record is written when a span closes, so a minutes-long background
    compile in flight at the end of a window is in no other reading) and
    ``dropped()`` counts ring evictions, so a reader can tell a sum over
    a ring that wrapped.
  * storage is a RING BUFFER (``TUPLEX_TRACE_BUFFER`` events, default
    65536): a long job keeps the most recent window instead of growing
    without bound. deque.append is atomic under the GIL, so recording
    takes no lock.
  * disabled (the default) the whole thing is one module-flag check:
    ``span()`` returns a shared no-op singleton — no allocation, no
    timestamp, no buffer write. Enable via the ``tuplex.tpu.trace``
    option or ``TUPLEX_TRACE=1``.
  * spans export as Chrome trace-event JSON (``export_chrome_trace`` /
    ``Metrics.export_trace``) openable in Perfetto or chrome://tracing —
    "X" complete events with ph/ts/dur/pid/tid, per-thread lanes named
    after the python thread, span attributes under ``args``.
  * multihost: every process records its own stream; ``set_host(idx)``
    keys the stream's pid lane by the jax process index and
    ``dump_jsonl``/``merge_jsonl`` let the driver merge per-host streams
    into one timeline (each host's lane keeps its own clock epoch; within
    a host, relative timing is exact).

The timebase is ``time.perf_counter`` relative to module import, reported
in microseconds (the Chrome trace unit).
"""

from __future__ import annotations

import contextlib
import hashlib
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Optional

_t0 = time.perf_counter()


def _env_enabled() -> bool:
    return os.environ.get("TUPLEX_TRACE", "0").strip().lower() \
        not in ("", "0", "false", "off")


def _capacity() -> int:
    try:
        return max(256, int(os.environ.get("TUPLEX_TRACE_BUFFER", "65536")))
    except ValueError:
        return 65536


_enabled = _env_enabled()
_events: "deque[dict]" = deque(maxlen=_capacity())
_tls = threading.local()
_host_pid: Optional[int] = None        # multihost lane (jax process index)
_tid_names: dict[int, str] = {}        # tid -> thread name (export metadata)
_ids = itertools.count(1)              # span serial; next() is GIL-atomic
_open: "dict[int, _Span]" = {}         # id -> span open now (open_spans)
_dropped = 0                           # ring evictions since clear()
_drop_lock = threading.Lock()          # taken only once the ring is full


def enabled() -> bool:
    return _enabled


def enable(on: bool = True) -> None:
    """Turn recording on/off process-wide. Turning off keeps already
    recorded events (export still works); ``clear()`` drops them."""
    global _enabled
    _enabled = bool(on)


def clear() -> None:
    """Drop the recorded events and the eviction count. Spans still open
    stay open (``open_spans``) and record when they close."""
    global _dropped
    _events.clear()
    _tid_names.clear()
    _dropped = 0


def dropped() -> int:
    """Records the ring evicted since ``clear()``: anything but 0 means a
    sum over ``events()`` misses the oldest part of the window."""
    return _dropped


def _record(rec: dict) -> None:
    global _dropped
    if len(_events) == _events.maxlen:
        with _drop_lock:
            _dropped += 1
    _events.append(rec)


def set_host(idx: int) -> None:
    """Key this process's span stream by a host index (multihost: the jax
    process index) so merged traces show one lane per host."""
    global _host_pid
    _host_pid = int(idx)


def set_stream(tag: Optional[str]) -> None:
    """Tag every span recorded by THIS thread with a stream id. The job
    service (serve/) sets the running job's id around each scheduler step,
    so concurrent tenants sharing one process separate into per-job span
    streams without per-tenant ring buffers. None clears the tag."""
    _tls.stream = None if tag is None else str(tag)


def current_stream() -> Optional[str]:
    return getattr(_tls, "stream", None)


def events_for_stream(tag: str) -> list:
    """Spans recorded under ``set_stream(tag)`` — one tenant's slice of
    the shared ring buffer (serve/: per-job Metrics/trace isolation)."""
    return [e for e in events() if e.get("stream") == tag]


def now_us() -> float:
    """Microseconds since the trace epoch (module import)."""
    return (time.perf_counter() - _t0) * 1e6


def to_trace_us(perf_s: float) -> float:
    """Convert a raw ``time.perf_counter()`` reading to microseconds on
    the trace clock. Both clocks share the perf_counter timebase, so
    scheduler stamps (JobRecord.t_submit/t_start) and span timestamps
    become directly comparable — runtime/critpath uses this to bound a
    job's running window on the span timeline."""
    return (perf_s - _t0) * 1e6


class _NoopSpan:
    """Shared do-nothing span for the disabled path: entering, exiting and
    setting attributes all fall through. One module-level instance — a
    disabled ``span()`` (or ``adopt(None)``) call allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, key, value):
        return self


NOOP = _NoopSpan()


def _cause() -> tuple:
    """(parent id, job id) for something starting now on this thread: the
    innermost open span, else what the thread adopted, else nothing."""
    stack = getattr(_tls, "stack", None)
    if stack:
        top = stack[-1]
        return top.id, top.job
    return getattr(_tls, "adopted", None) or (None, None)


class _Span:
    __slots__ = ("name", "cat", "args", "id", "parent", "job", "tid",
                 "_ts", "_depth")

    def __init__(self, name: str, cat: str, args: Optional[dict]):
        self.name = name
        self.cat = cat
        self.args = args
        self.id = 0
        self.parent = None
        self.job = None
        self.tid = 0
        self._ts = 0.0
        self._depth = 0

    def set(self, key: str, value: Any) -> "_Span":
        """Attach one attribute (rendered under ``args`` in the export).
        Callable mid-span — cache hit/miss verdicts land on the span that
        covered the lookup."""
        a = self.args
        if a is None:
            a = self.args = {}
        a[key] = value
        return self

    def __enter__(self) -> "_Span":
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self._depth = len(stack)
        self.id = next(_ids)
        self.parent, self.job = _cause()
        if self.job is None and self.name == "job":
            self.job = self.id             # the root of its own job
        self.tid = threading.get_ident()
        stack.append(self)
        _open[self.id] = self
        self._ts = now_us()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        dur = now_us() - self._ts
        _open.pop(self.id, None)
        stack = getattr(_tls, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        elif stack and self in stack:          # pragma: no cover - misuse
            stack.remove(self)
        if et is not None:
            self.set("error", et.__name__)
        tid = self.tid
        if tid not in _tid_names:
            _tid_names[tid] = threading.current_thread().name
        rec = {
            "name": self.name, "cat": self.cat,
            "ts": self._ts, "dur": dur,
            "tid": tid, "depth": self._depth,
            "id": self.id, "parent": self.parent, "job": self.job,
            "args": self.args,
        }
        st = current_stream()
        if st is not None:
            rec["stream"] = st
        _record(rec)
        return False


def span(name: str, cat: str = "exec", args: Optional[dict] = None):
    """Open a span. ``with tracing.span("stage:dispatch", "exec") as sp:``
    — the span closes (and is recorded) when the block exits; ``sp.set``
    attaches attributes. When tracing is disabled this returns a shared
    no-op object: zero allocation, zero bookkeeping."""
    if not _enabled:
        return NOOP
    return _Span(name, cat, args)


def pulls(it, name: str, cat: str = "exec"):
    """Iterate `it` with each pull — the work a generator does to produce
    one item — inside its own span; `rows` is the item's ``num_rows``
    where it has one. A generator cannot hold one span open across its
    yields (the consumer's spans would nest inside it)."""
    it = iter(it)
    while True:
        with span(name, cat) as sp:
            try:
                item = next(it)
            except StopIteration:
                return
            if sp is not NOOP:
                sp.set("rows", getattr(item, "num_rows", None))
        yield item


# -- cause across threads ----------------------------------------------------

def handoff():
    """On the SUBMITTING thread: what a worker needs to put its spans down
    to this thread's work — (parent id, job id) of the innermost span open
    here (or of what this thread itself adopted). None when tracing is
    off or nothing is open: ``adopt(None)`` then does nothing."""
    if not _enabled:
        return None
    parent, job = _cause()
    return None if parent is None else (parent, job)


class _Adopted:
    """``with adopt(h):`` on the worker — top-level spans opened inside
    take `h` as parent and job. Restores what was adopted before on exit
    (pool workers are reused)."""

    __slots__ = ("_h", "_prev")

    def __init__(self, h: tuple):
        self._h = h
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_tls, "adopted", None)
        _tls.adopted = self._h
        return self

    def __exit__(self, *exc) -> bool:
        _tls.adopted = self._prev
        return False


def adopt(h):
    """Worker side of ``handoff()``; ``adopt(None)`` is the shared no-op."""
    if h is None:
        return NOOP
    return _Adopted(h)


def open_spans() -> list[dict]:
    """Snapshot of the spans open NOW on every thread, oldest first: name,
    cat, ts (us), tid, id, parent, job, args. A reader at the end of a
    window sees here what the ring cannot hold yet — a background compile
    still in flight."""
    return [{"name": s.name, "cat": s.cat, "ts": s._ts, "tid": s.tid,
             "id": s.id, "parent": s.parent, "job": s.job,
             "args": dict(s.args) if s.args else None}
            for s in sorted(list(_open.values()), key=lambda s: s.id)]


def _point(name: str, cat: str, ts: float, dur, args) -> None:
    """One record that is not a context-managed span (instant/complete)."""
    tid = threading.get_ident()
    if tid not in _tid_names:
        _tid_names[tid] = threading.current_thread().name
    parent, job = _cause()
    ev = {"name": name, "cat": cat, "ts": ts, "dur": dur, "tid": tid,
          "depth": len(getattr(_tls, "stack", ())),
          "id": next(_ids), "parent": parent, "job": job, "args": args}
    st = current_stream()
    if st is not None:
        ev["stream"] = st
    _record(ev)


def instant(name: str, cat: str = "exec",
            args: Optional[dict] = None) -> None:
    """Record a zero-duration marker (Chrome 'i' instant event)."""
    if not _enabled:
        return
    _point(name, cat, now_us(), None, args)


def complete(name: str, cat: str, ts_us: float, dur_us: float,
             args: Optional[dict] = None) -> None:
    """Record an interval with EXPLICIT timestamps — for waits measured
    across threads (a pool job's queue wait starts on the submitting
    thread and ends on the worker) where a context manager can't
    bracket the gap."""
    if not _enabled:
        return
    _point(name, cat, float(ts_us), float(dur_us), args)


# -- names for what runs on the device ---------------------------------------

def key8(*parts) -> str:
    """First 8 hex digits of a hash over `parts`' reprs: stable across
    processes (never an id() or a counter)."""
    return hashlib.sha256(repr(parts).encode()).hexdigest()[:8]


def fn_key8(fn, *fallback) -> str:
    """The key8 `fn` was named with by ``name_fn`` (a wrapper takes its
    stage's), else ``key8`` of its name and `fallback`."""
    name = getattr(fn, "__name__", "")
    if name.startswith("tpx_"):
        return name.rsplit("_", 1)[-1]
    return key8(name, *fallback)


def name_fn(fn, role: str, key: str):
    """Give `fn` a stable ``__name__`` BEFORE it is jitted, so its HLO
    module and the profiler's `XLA Modules` line read
    ``jit_tpx_<role>_<key8>``. `key` is 8+ hex digits stable across
    processes (a stage's ``key()``, or ``key8`` of the builder's
    arguments). The name is no part of the content fingerprint
    (exec/compilequeue hashes the jaxpr text)."""
    fn.__name__ = fn.__qualname__ = f"tpx_{role}_{key[:8]}"
    return fn


_NULL_CM = contextlib.nullcontext()   # shared, stateless


def device_annotation(name: str):
    """``jax.profiler.TraceAnnotation`` bracketing a device-side region so
    our host spans line up inside XLA device profiles
    (``tuplex.tpu.profileDir``). No-op (shared null context — zero
    allocation, like NOOP) when tracing is off or the profiler API is
    unavailable — annotation must never fail a dispatch."""
    if not _enabled:
        return _NULL_CM
    try:
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(name)
    except Exception:   # pragma: no cover - profiler API drift
        return _NULL_CM


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def events() -> list[dict]:
    """Snapshot of the recorded span records (ring-buffer order: oldest
    first). Each record: name/cat/ts/dur(us)/tid/depth/id/parent/job/args.

    Recording stays lock-free, so a compile-pool (or abandoned deadline-
    compile) thread can append mid-snapshot — deques raise RuntimeError on
    mutation during iteration; retry until a consistent pass succeeds."""
    while True:
        try:
            return list(_events)
        except RuntimeError:       # pragma: no cover - needs a mid-iter race
            continue


def events_since(ts_us: float) -> list[dict]:
    """Spans that STARTED at or after `ts_us` (history per-job slicing)."""
    return [e for e in events() if e["ts"] >= ts_us]


def _chrome_event(e: dict, pid: int) -> dict:
    out = {"name": e["name"], "cat": e.get("cat") or "exec",
           "ph": "X" if e.get("dur") is not None else "i",
           "ts": round(float(e["ts"]), 3),
           "pid": pid, "tid": e.get("tid", 0)}
    if e.get("dur") is not None:
        out["dur"] = round(float(e["dur"]), 3)
    else:
        out["s"] = "t"                      # instant scope: thread
    if e.get("args"):
        out["args"] = e["args"]
    if e.get("stream") is not None:
        # per-tenant stream tag (serve/): copy-on-write so the recorded
        # event's args dict is never mutated by the export
        out["args"] = dict(out.get("args") or {}, stream=e["stream"])
    return out


def chrome_events(evts: Optional[list] = None,
                  pid: Optional[int] = None) -> list[dict]:
    """Convert span records to Chrome trace-event dicts, prefixed with
    process/thread name metadata events so Perfetto labels the lanes."""
    if evts is None:
        evts = events()
    p = pid if pid is not None \
        else (_host_pid if _host_pid is not None else os.getpid())
    out: list[dict] = [{
        "name": "process_name", "ph": "M", "pid": p, "tid": 0,
        "args": {"name": f"tuplex_tpu host{_host_pid}"
                 if _host_pid is not None else "tuplex_tpu"}}]
    # .copy() is atomic under the GIL — a concurrent thread closing its
    # FIRST span inserts here, and plain .items() iteration would raise
    for tid, tname in _tid_names.copy().items():
        out.append({"name": "thread_name", "ph": "M", "pid": p,
                    "tid": tid, "args": {"name": tname}})
    out.extend(_chrome_event(e, p) for e in evts)
    return out


def export_chrome_trace(path: str, extra_events: Optional[list] = None) -> str:
    """Write the recorded spans as a Chrome trace-event JSON file (the
    ``{"traceEvents": [...]}`` object form) loadable in Perfetto /
    chrome://tracing. `extra_events` (already chrome-shaped dicts — e.g.
    other hosts' streams via ``load_jsonl``) merge into the same file."""
    evs = chrome_events()
    if extra_events:
        evs.extend(extra_events)
    obj = {"traceEvents": evs, "displayTimeUnit": "ms"}
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fp:
        json.dump(obj, fp)
    os.replace(tmp, path)
    return path


def dump_jsonl(path: str) -> str:
    """Write this process's span stream as JSON-lines of chrome-shaped
    events (one event per line; a multihost worker dumps its stream here
    for the driver to merge)."""
    with open(path, "w") as fp:
        for e in chrome_events():
            fp.write(json.dumps(e) + "\n")
    return path


def load_jsonl(path: str) -> list[dict]:
    out = []
    with open(path) as fp:
        for line in fp:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out


def merge_jsonl(paths: list, out_path: str) -> str:
    """Driver-side merge: this process's spans + every per-host stream
    (``dump_jsonl`` files) into one Chrome trace. Lanes separate by pid
    (the host index), so cross-host skew never corrupts within-host
    nesting."""
    extra: list[dict] = []
    for p in paths:
        try:
            extra.extend(load_jsonl(p))
        except OSError:
            continue
    return export_chrome_trace(out_path, extra_events=extra)
