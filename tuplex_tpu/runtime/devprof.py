"""Device-plane cost attribution: XLA cost/memory analysis + measured
device time + roofline readouts.

Every observability layer so far is host-side wall clock (spans, serve
histograms); nothing answers "what does this compiled stage cost ON THE
DEVICE" — FLOPs, bytes moved, peak memory, achieved utilization — which
is exactly the signal a cost-based plan optimizer needs. Three pieces:

* **StageCost** — harvested once per compiled executable from XLA's own
  ``compiled.cost_analysis()`` / ``compiled.memory_analysis()`` (guarded
  per backend: XLA:CPU returns partial dicts on some versions, TPU
  plugins may return nothing). The compile queue calls ``note_compiled``
  at its publish chokepoint, so AOT hits, dedup hits and subprocess
  handbacks all land here; the record is persisted as a ``<fp>.cost.json``
  sidecar NEXT TO the content-addressed executable artifact, so a warm
  second process recovers the analysis with zero recompiles — the AOT
  store becomes a queryable cost database, not a pile of opaque blobs.
* **measured device time** — a dispatch returns at launch; the collect
  side (exec/local) waits for its outputs where the host needs them and
  records, per stage, the seconds from the launch (or from the moment
  the stage's previous dispatch was seen ready, whichever is later: the
  chip runs one at a time) to the moment this one was seen ready, split
  cold (first call: includes the compile/AOT-load wait) vs warm. Where
  the host waited that is the chip's time for the partition to the
  poll's 0.2 ms; where the host arrived after the chip it is an upper
  bound and the sample is flagged ``late``. Samples land in telemetry
  histograms (``device_dispatch_seconds{stage,state}``) and a per-stage
  accumulator consumed into stage metrics.
* **roofline** — a small per-platform peak table (TPU generations from
  published specs; CPU a labeled estimate) turns flops/bytes/seconds
  into achieved FLOP/s, achieved bytes/s, arithmetic intensity and
  fraction-of-attainable-peak per stage, plus peak-memory vs the job's
  MemoryManager budget.

Disabled (``TUPLEX_DEVPROF=0`` env kill switch) the record path is one
module-flag check — no allocation, no lock (the same zero-overhead
contract tracing/telemetry pin, test-asserted). The switch turns the
RECORDING off and nothing else: the collect-side wait is part of the
schedule (the host needs the outputs there either way), so a stage
launches and collects in the same order with attribution on or off.
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
from dataclasses import asdict, dataclass
from typing import Optional

# ---------------------------------------------------------------------------
# enable gate (mirrors runtime/telemetry: process-wide, env kill switch wins)
# ---------------------------------------------------------------------------


def _env_disabled() -> bool:
    return os.environ.get("TUPLEX_DEVPROF", "").strip().lower() \
        in ("0", "false", "off")


_enabled = not _env_disabled()


def enabled() -> bool:
    return _enabled


def enable(on: bool = True) -> None:
    """Process-wide gate. TUPLEX_DEVPROF=0 wins over any option-driven
    enable (A/B overhead timing of the recording itself)."""
    global _enabled
    _enabled = bool(on) and not _env_disabled()


def apply_options(options) -> None:
    """Wire the process gate from ContextOptions. Like telemetry, the
    ``tuplex.tpu.devprof`` option turns attribution ON, never off — the
    gate is process-wide and another live Context may depend on it; the
    only OFF switches are the env kill switch and an explicit
    ``devprof.enable(False)``."""
    if options.get_bool("tuplex.tpu.devprof", True):
        enable(True)


# ---------------------------------------------------------------------------
# StageCost: the per-executable analysis record
# ---------------------------------------------------------------------------


@dataclass
class StageCost:
    """XLA's static cost/memory analysis for ONE compiled executable
    (per-execution numbers: one dispatch of one partition batch).
    ``partial`` marks records where one of the two analyses was
    unavailable; a missing record altogether means the backend returned
    nothing (compilestats flags those explicitly)."""

    flops: float = 0.0
    bytes_accessed: float = 0.0
    transcendentals: float = 0.0
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    generated_code_bytes: int = 0
    backend: str = ""
    partial: bool = False

    @property
    def peak_bytes(self) -> int:
        """Peak device-memory footprint of one execution: arguments +
        outputs + XLA temp allocations + generated code. XLA does not
        expose a liveness-exact peak through this API; the sum is the
        upper bound the runtime actually reserves."""
        return (self.argument_bytes + self.output_bytes + self.temp_bytes
                + self.generated_code_bytes)

    def to_dict(self) -> dict:
        return asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "StageCost":
        fields = {k: d[k] for k in cls.__dataclass_fields__ if k in d}
        return cls(**fields)


def harvest(compiled) -> Optional[StageCost]:
    """Pull XLA's cost + memory analysis off a compiled executable,
    tolerating every observed shape of the API: ``cost_analysis()``
    returning a dict, a list of per-device dicts, ``None``, or raising
    (some PJRT plugins); ``memory_analysis()`` likewise. Returns None
    only when NEITHER analysis yields anything — the "backend returned
    nothing" case the CLI flags."""
    ca: Optional[dict] = None
    try:
        raw = compiled.cost_analysis()
        if isinstance(raw, (list, tuple)):
            raw = raw[0] if raw else None
        if isinstance(raw, dict) and raw:
            ca = raw
    except Exception:
        ca = None
    ma = None
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ca is None and ma is None:
        return None
    try:
        import jax

        backend = jax.default_backend()
    except Exception:   # pragma: no cover - no backend yet
        backend = ""
    cost = StageCost(backend=backend, partial=(ca is None or ma is None))
    if ca is not None:
        cost.flops = float(ca.get("flops", 0.0) or 0.0)
        cost.bytes_accessed = float(ca.get("bytes accessed", 0.0) or 0.0)
        cost.transcendentals = float(ca.get("transcendentals", 0.0) or 0.0)
    if ma is not None:
        for attr, field in (("argument_size_in_bytes", "argument_bytes"),
                            ("output_size_in_bytes", "output_bytes"),
                            ("temp_size_in_bytes", "temp_bytes"),
                            ("generated_code_size_in_bytes",
                             "generated_code_bytes")):
            try:
                setattr(cost, field, int(getattr(ma, attr, 0) or 0))
            except Exception:
                pass
    return cost


# ---------------------------------------------------------------------------
# sidecar persistence (alongside the content-addressed AOT artifact)
# ---------------------------------------------------------------------------


def _sidecar_path(fp: str) -> Optional[str]:
    if not fp:
        return None
    from .jaxcfg import aot_cache_dir

    d = aot_cache_dir()
    return os.path.join(d, fp + ".cost.json") if d else None


def store_cost(fp: str, cost: StageCost) -> None:
    """Persist the analysis next to ``<fp>.aot`` so a warm process (AOT
    hit, zero compiles) recovers it without re-analyzing."""
    path = _sidecar_path(fp)
    if path is None:
        return
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(cost.to_dict(), f)
        os.replace(tmp, path)
    except OSError:   # pragma: no cover - sidecar is best-effort
        pass


def load_cost(fp: str) -> Optional[StageCost]:
    path = _sidecar_path(fp)
    if path is None or not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            return StageCost.from_dict(json.load(f))
    except Exception:   # pragma: no cover - corrupt sidecar = miss
        return None


# ---------------------------------------------------------------------------
# in-process registry: fingerprint -> cost, stage tag -> {fp: cost}
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_BY_FP: dict[str, Optional[StageCost]] = {}    # None = analysis unavailable
_BY_TAG: dict[str, dict] = {}                  # tag -> {fp_or_'': cost|None}
_MAX_ENTRIES = 4096


def note_compiled(tag: str, fp: str, compiled) -> None:
    """Publish chokepoint hook (exec/compilequeue): associate `tag` (the
    stage cache key) with `fp`'s analysis — loading the sidecar on an AOT
    hit, harvesting (and persisting) on a fresh compile or handback. A
    backend that returns nothing is recorded as None so the stage reads
    as "analysis unavailable" rather than silently blank."""
    if not _enabled:
        return
    with _LOCK:
        have = fp in _BY_FP if fp else False
        cost = _BY_FP.get(fp) if have else None
    if not have:
        cost = load_cost(fp) if fp else None
        freshly_harvested = False
        if cost is None:
            cost = harvest(compiled)
            freshly_harvested = cost is not None
        if fp and freshly_harvested:
            store_cost(fp, cost)
    with _LOCK:
        if fp:
            _BY_FP[fp] = cost
            while len(_BY_FP) > _MAX_ENTRIES:
                _BY_FP.pop(next(iter(_BY_FP)))
        if tag:
            _BY_TAG.setdefault(tag, {})[fp] = cost
            while len(_BY_TAG) > _MAX_ENTRIES:
                _BY_TAG.pop(next(iter(_BY_TAG)))


def note_tag(tag: str, fp: str) -> None:
    """Dedup-hit association: the executable (and its cost) already
    exist; only the tag->fp edge is new."""
    if not _enabled or not tag or not fp:
        return
    with _LOCK:
        if fp in _BY_FP:
            _BY_TAG.setdefault(tag, {})[fp] = _BY_FP[fp]


def cost_for_tag(tag: str) -> Optional[StageCost]:
    """The stage's dominant executable's analysis: a tag may map to
    several fingerprints (packed main fn, ragged-tail shapes, general
    tier, cpu pin) — the max-flops record is the one dispatch spends its
    time in."""
    with _LOCK:
        recs = [c for c in _BY_TAG.get(tag, {}).values() if c is not None]
    if not recs:
        return None
    return max(recs, key=lambda c: (c.flops, c.bytes_accessed))


def tag_seen(tag: str) -> bool:
    """True when at least one executable compiled under `tag` (even if
    its backend returned no analysis)."""
    with _LOCK:
        return tag in _BY_TAG


# ---------------------------------------------------------------------------
# measured device time per dispatch
# ---------------------------------------------------------------------------

#: one stage-label truncation for EVERY exposition surface (histogram
#: labels, gauge labels) so a PromQL join across the devprof families
#: matches — stage.key() is 16 hex chars, so 16 keeps it whole
STAGE_LABEL_LEN = 16

# (owner, tag) -> accumulator, consumed per stage execution. The owner
# half (the dispatching backend's id) keeps CONCURRENT serve jobs
# running isomorphic stages — identical stage.key() by design, that is
# what compile-sharing means — from pooling samples into one window and
# having whichever job finishes first steal the others' report.
_DISP: dict[tuple, dict] = {}
_WARM_KEEP = 64                     # bounded warm-sample window per stage


def block_ready(outs) -> bool:
    """Wait until a dispatch's device work is done — by POLLING
    ``Array.is_ready()``, never ``jax.block_until_ready``. The
    distinction is load-bearing: block_until_ready touches the result
    buffers, and on XLA:CPU with input donation forced on
    (TUPLEX_DONATE=1 — a config jax itself doesn't support on CPU) that
    touch non-deterministically corrupted stage outputs (missing filter
    survivors, garbage '#keep' lattices; reproduced only via
    block_until_ready — an is_ready poll or a plain sleep over the same
    window is clean). Polling observes completion without touching
    buffer internals, at ±0.2 ms precision — noise next to the
    histogram's ±12% buckets. Handles the packed wire's PackedOuts
    (buf/vbuf/extras attributes — not a pytree) and plain pytrees.

    Returns whether every output was ready at the first look (the host
    arrived after the chip: nothing was waited for). Called by the
    collect side with attribution on or off — it is the job thread's
    wait for the outputs, not a measurement — so what a poll raises
    propagates: a dispatch that failed asynchronously fails its task
    there, as a failed fetch does."""
    import jax

    buf = getattr(outs, "buf", None)
    if buf is not None:
        outs = (buf, getattr(outs, "vbuf", None),
                getattr(outs, "extras", None))
    on_arrival = True
    for leaf in jax.tree_util.tree_leaves(outs):
        ready = getattr(leaf, "is_ready", None)
        if ready is None:
            continue
        while not ready():
            on_arrival = False
            time.sleep(0.0002)
    return on_arrival


def record_dispatch(tag: str, seconds: float, cold: bool = False,
                    rows: int = 0, owner: int = 0,
                    late: bool = False) -> None:
    """One launched-partition sample: seconds from the launch (or the
    stage's previous dispatch seen ready, the later of the two) to this
    one seen ready. `cold` marks the first call of an input spec
    (includes the compile / AOT-load / dedup wait) so roofline math
    prefers warm samples (see stage_report for the cold-only fallback).
    `late` marks a sample whose outputs were ready when the host came
    for them: an upper bound on the chip's time (it holds host work
    too), counted in the sums and the histogram but kept out of the warm
    median that feeds `roofline_frac`. `owner` scopes the accumulator to
    the dispatching backend so concurrent jobs sharing a stage key don't
    pool windows."""
    if not _enabled or not tag or seconds < 0:
        return
    from . import telemetry

    telemetry.observe("device_dispatch_seconds", seconds,
                      stage=tag[:STAGE_LABEL_LEN],
                      state="cold" if cold else "warm")
    with _LOCK:
        key = (owner, tag)
        acc = _DISP.get(key)
        if acc is None:
            # bounded like every other registry here: a stage that
            # dispatches but dies before its stage_report consume (job
            # crash/interrupt) must not leak its window forever in a
            # long-lived serve process
            while len(_DISP) >= _MAX_ENTRIES:
                _DISP.pop(next(iter(_DISP)))
            acc = _DISP[key] = {"device_s": 0.0, "cold_s": 0.0, "n": 0,
                                "cold_n": 0, "rows": 0, "warm": [],
                                "min_s": math.inf}
        acc["device_s"] += seconds
        acc["n"] += 1
        acc["rows"] += int(rows)
        if seconds < acc["min_s"]:
            acc["min_s"] = seconds
        if cold:
            acc["cold_s"] += seconds
            acc["cold_n"] += 1
        elif not late and len(acc["warm"]) < _WARM_KEEP:
            acc["warm"].append(seconds)


# ---------------------------------------------------------------------------
# platform peaks + roofline math
# ---------------------------------------------------------------------------


@dataclass
class Peaks:
    flops_per_s: float
    bytes_per_s: float
    name: str = ""
    # "table" (published spec) | "estimate" (CPU) | "override" (env) |
    # "unknown" (an accelerator not in _TPU_PEAKS: no peak, no share)
    kind: str = "estimate"


#: published per-chip peaks (dense compute, HBM bandwidth) by device-kind
#: substring; matched case-insensitively against jax's device_kind
_TPU_PEAKS = (
    ("v6e", 918e12, 1640e9),
    ("v5p", 459e12, 2765e9),
    ("v5e", 197e12, 819e9),
    ("v5 lite", 197e12, 819e9),
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 46e12, 700e9),
)

_peaks_cache: Optional[Peaks] = None


def platform_peaks() -> Peaks:
    """Peak FLOP/s + memory bytes/s for the default device.
    TUPLEX_DEVPROF_PEAKS="<flops>,<bytes_per_s>" overrides (roofline
    calibration on unlisted hardware); TPU generations come from the
    published spec table, and an accelerator absent from it is labeled
    "unknown" with no peaks (roofline() then reports achieved rates
    only); CPU is a labeled ESTIMATE (cores x 3 GHz x 16
    f32 FMA lanes, ~25 GB/s stream bandwidth) — good enough to rank
    stages, not to certify utilization."""
    global _peaks_cache
    if _peaks_cache is not None:
        return _peaks_cache
    env = os.environ.get("TUPLEX_DEVPROF_PEAKS", "")
    if env:
        try:
            f, b = (float(x) for x in env.split(",")[:2])
            _peaks_cache = Peaks(f, b, name="env", kind="override")
            return _peaks_cache
        except ValueError:
            pass
    kind_s = ""
    backend = "cpu"
    try:
        import jax

        dev = jax.devices()[0]
        backend = dev.platform
        kind_s = str(getattr(dev, "device_kind", "")).lower()
    except Exception:   # pragma: no cover - no backend yet
        pass
    if backend != "cpu":
        for sub, f, b in _TPU_PEAKS:
            if sub in kind_s:
                _peaks_cache = Peaks(f, b, name=kind_s, kind="table")
                return _peaks_cache
        # an accelerator that is not in the table has no peak here and
        # gets no roofline share — never another generation's numbers
        _peaks_cache = Peaks(0.0, 0.0, name=kind_s or backend,
                             kind="unknown")
        return _peaks_cache
    cores = os.cpu_count() or 1
    _peaks_cache = Peaks(cores * 3.0e9 * 16, 25e9,
                         name=f"cpu x{cores}", kind="estimate")
    return _peaks_cache


def roofline(flops: float, nbytes: float, seconds: float,
             peaks: Optional[Peaks] = None) -> dict:
    """The classic roofline readout for one execution: achieved FLOP/s
    and bytes/s, arithmetic intensity (flops/byte), the attainable peak
    ``min(peak_flops, intensity * peak_bw)`` and the achieved fraction of
    it, clamped to (0, 1]. A flop-free stage (pure data movement) reads
    off the bandwidth roof instead. Empty dict when `seconds` (or both
    numerators) is unusable."""
    if seconds <= 0 or not math.isfinite(seconds):
        return {}
    peaks = peaks or platform_peaks()
    out: dict = {}
    if peaks.kind == "unknown":     # achieved rates only: no roof to
        if flops > 0:               # take a share of
            out["achieved_flops_per_s"] = flops / seconds
        if nbytes > 0:
            out["achieved_bytes_per_s"] = nbytes / seconds
        return out
    if flops > 0:
        ach_f = flops / seconds
        out["achieved_flops_per_s"] = ach_f
        if nbytes > 0:
            intensity = flops / nbytes
            out["arithmetic_intensity"] = intensity
            attain = min(peaks.flops_per_s, intensity * peaks.bytes_per_s)
        else:
            attain = peaks.flops_per_s
        out["attainable_flops_per_s"] = attain
        out["roofline_frac"] = min(1.0, ach_f / attain) if attain > 0 \
            else 0.0
    if nbytes > 0:
        ach_b = nbytes / seconds
        out["achieved_bytes_per_s"] = ach_b
        if flops <= 0:
            out["arithmetic_intensity"] = 0.0
            out["roofline_frac"] = min(1.0, ach_b / peaks.bytes_per_s) \
                if peaks.bytes_per_s > 0 else 0.0
    return out


# ---------------------------------------------------------------------------
# the per-stage report (consumed into stage metrics)
# ---------------------------------------------------------------------------

_REPORTS: dict[str, dict] = {}          # tag -> last report (exposition)
_MAX_REPORTS = 256


def stage_report(tag: str, mm_budget: int = 0,
                 owner: int = 0) -> Optional[dict]:
    """Consume the stage's dispatch window and combine it with the
    executable's StageCost into FLAT NUMERIC metrics (they ride the
    stage metrics dict through Metrics.stage_breakdown unchanged):

    device_s / device_cold_s / device_dispatches, flops / device_bytes
    (analysis x dispatch count), hbm_peak (per-execution peak footprint),
    roofline_frac (warm-median seconds vs the platform roof; a stage
    whose every sample was cold or `late` falls back to the SMALLEST
    sample — still compile/load- or host-inclusive, so it UNDERSTATES
    utilization — warm waited-for runs self-correct it), and
    hbm_budget_frac when the MemoryManager budget
    is known. Also updates the bounded exposition snapshot (telemetry
    /metrics gauges)."""
    if not _enabled or not tag:
        return None
    with _LOCK:
        acc = _DISP.pop((owner, tag), None)
    if acc is None or acc["n"] == 0:
        return None
    cost = cost_for_tag(tag)
    rep: dict = {
        "device_s": acc["device_s"],
        "device_cold_s": acc["cold_s"],
        "device_dispatches": acc["n"],
    }
    warm = sorted(acc["warm"])
    warm_med = warm[len(warm) // 2] if warm else 0.0
    if cost is not None:
        rep["flops"] = cost.flops * acc["n"]
        rep["device_bytes"] = cost.bytes_accessed * acc["n"]
        rep["hbm_peak"] = cost.peak_bytes
        # cold- or late-only fallback: the smallest observed sample is
        # the least compile/load/host-inflated one (a mean over cold
        # samples would bury the execution under the compile wait)
        rl = roofline(cost.flops, cost.bytes_accessed,
                      warm_med if warm_med > 0 else acc["min_s"])
        if "roofline_frac" in rl:
            rep["roofline_frac"] = rl["roofline_frac"]
        if "arithmetic_intensity" in rl:
            rep["arithmetic_intensity"] = rl["arithmetic_intensity"]
        if "achieved_flops_per_s" in rl:
            rep["achieved_flops_per_s"] = rl["achieved_flops_per_s"]
        if mm_budget > 0:
            # vs the JOB's MemoryManager budget (tuplex.executorMemory /
            # the serve per-job memory cap) — a capacity-planning signal,
            # not a device-HBM measurement on CPU backends
            rep["hbm_budget_frac"] = cost.peak_bytes / mm_budget
    with _LOCK:
        _REPORTS[tag] = dict(rep)
        while len(_REPORTS) > _MAX_REPORTS:
            _REPORTS.pop(next(iter(_REPORTS)))
    _index_update(tag, rep, cost)
    return rep


def reports() -> dict:
    """Last report per stage tag (the /metrics exposition source)."""
    with _LOCK:
        return {t: dict(r) for t, r in _REPORTS.items()}


# ---------------------------------------------------------------------------
# the persistent stage index (compilestats' plan-time lookup)
# ---------------------------------------------------------------------------

_INDEX_NAME = "devprof_stages.json"
_INDEX_MAX = 512
#: min seconds between full index rewrites per process — the index is a
#: read-parse-rewrite of one JSON file, so a busy serve loop must not
#: pay O(index) disk I/O on every stage consume. A tag not yet in the
#: index always writes through (first measurement beats freshness).
_INDEX_WRITE_EVERY_S = 5.0
_index_last_write = 0.0
_index_known: set = set()           # tags this process already indexed


def _index_path() -> Optional[str]:
    from .jaxcfg import aot_cache_dir

    d = aot_cache_dir()
    return os.path.join(d, _INDEX_NAME) if d else None


def _index_update(tag: str, rep: dict, cost: Optional[StageCost]) -> None:
    """Fold one stage report into the on-disk tag index. ``stage.key()``
    is content-derived (ops + UDF sources + schema), so a later
    ``compilestats`` run planning the same script computes the same tag
    and finds the measured record without executing anything."""
    path = _index_path()
    if path is None:
        return
    global _index_last_write
    now = time.monotonic()
    if tag in _index_known \
            and now - _index_last_write < _INDEX_WRITE_EVERY_S:
        return          # refresh later; the in-memory report is current
    try:
        idx = load_stage_index()
        entry = {"updated": time.time(),
                 "device_s_per_dispatch":
                     rep["device_s"] / max(1, rep["device_dispatches"]),
                 "device_dispatches": rep["device_dispatches"],
                 "roofline_frac": rep.get("roofline_frac"),
                 "analysis": cost.to_dict() if cost is not None else None}
        idx[tag] = entry
        if len(idx) > _INDEX_MAX:
            for k, _ in sorted(idx.items(),
                               key=lambda kv: kv[1].get("updated", 0)) \
                    [: len(idx) - _INDEX_MAX]:
                idx.pop(k, None)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(idx, f)
        os.replace(tmp, path)      # atomic; cross-process last-writer-
        _index_known.add(tag)      # wins is acceptable for a best-
        _index_last_write = now    # effort measurement index
    except Exception:   # pragma: no cover - index is best-effort
        pass


def load_stage_index() -> dict:
    """tag -> {device_s_per_dispatch, analysis|None, ...} from the cache
    dir (empty when nothing ever ran)."""
    path = _index_path()
    if path is None or not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            d = json.load(f)
        return d if isinstance(d, dict) else {}
    except Exception:   # pragma: no cover - corrupt index = empty
        return {}


# ---------------------------------------------------------------------------
# lifecycle (tests)
# ---------------------------------------------------------------------------


def clear() -> None:
    global _peaks_cache, _index_last_write
    with _LOCK:
        _BY_FP.clear()
        _BY_TAG.clear()
        _DISP.clear()
        _REPORTS.clear()
    _index_known.clear()
    _index_last_write = 0.0
    _peaks_cache = None


# human-readable helpers — ONE threshold ladder for every surface that
# prints flops/bytes counts (compilestats, the dashboard device table)

def fmt_eng(v: float, unit: str = "") -> str:
    """Engineering notation: 1.2G / 3.4M / 5.6k; with a unit the number
    gets a separating space ("1.2 GFLOP")."""
    v = float(v)
    sep = " " if unit else ""
    for prefix, div in (("G", 1e9), ("M", 1e6), ("k", 1e3)):
        if abs(v) >= div:
            return f"{v / div:.1f}{sep}{prefix}{unit}"
    return f"{v:.0f}{sep}{unit}"


def fmt_flops(v: float) -> str:
    return fmt_eng(v, "FLOP")


def fmt_bytes(v: float) -> str:
    return fmt_eng(v, "B")
