"""Reduction of a `jax.profiler` trace (`.xplane.pb`) to the device
metrics: busy seconds for each device, device operations by total time, and
idle gaps named by the program span that was open on the host.

Read with `jax.profiler.ProfileData` alone. A device is a plane named
`/device:TPU:<n>`; its line `XLA Ops` holds one event for each operation run
(`XLA Modules` one for each executable). Host threads are lines of the plane
`/host:CPU`; a `TraceAnnotation` lands on the line of the Python thread that
emitted it. Times are nanoseconds from the start of the profile on every
plane; on the v5e the device's clock read about 1 ms ahead of the host's
(a module started "before" its launch), so gaps are named to that grain.
"""

from __future__ import annotations

import bisect
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "(no span open)"


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(plane, line_name: str) -> list:
    """[(start_ns, end_ns, name)] of one line, sorted by start."""
    out = []
    for line in plane.lines:
        if line.name == line_name:
            for ev in line.events:
                s = float(ev.start_ns)
                out.append((s, s + float(ev.duration_ns), ev.name))
    out.sort()
    return out


def device_planes(profile) -> list:
    """[(device id, plane)] in id order."""
    found = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            found.append((int(m.group(1)), plane))
    return sorted(found, key=lambda p: p[0])


def find_marker(profile, name: str):
    """Start (ns) of the first host event called `name`, or None."""
    best = None
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    s = float(ev.start_ns)
                    best = s if best is None else min(best, s)
    return best


def merge(intervals: list, lo: float, hi: float) -> list:
    """Sorted, disjoint [(start, end)] covering the same time inside
    [lo, hi]."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    """The complement of disjoint sorted `busy` inside [lo, hi]."""
    out = []
    cur = lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def self_segments(intervals: list, lo: float, hi: float,
                  outside: str = NO_SPAN) -> list:
    """[(start, end, name)] covering [lo, hi]: each instant goes to the
    innermost of the properly nested `intervals` [(start, end, name)] open
    then, or to `outside`. An interval's self time is its time less its
    children's."""
    segs: list = []
    stack: list = []
    cur = lo

    def emit(upto: float) -> None:
        nonlocal cur
        upto = min(upto, hi)
        if upto > cur:
            segs.append((cur, upto, stack[-1][2] if stack else outside))
            cur = upto

    for iv in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        s, e = iv[0], iv[1]
        if e <= lo or s >= hi or e <= s:
            continue
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        emit(s)
        stack.append(iv)
    while stack:
        emit(stack[-1][1])
        stack.pop()
    emit(hi)
    return segs


def overlap_by_name(windows: list, segs: list) -> dict:
    """Seconds (in the inputs' unit) of each segment name inside the
    disjoint sorted `windows`."""
    total: dict = {}
    starts = [s for s, _, _ in segs]
    for lo, hi in windows:
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        while i < len(segs) and segs[i][0] < hi:
            s, e, name = segs[i]
            d = min(e, hi) - max(s, lo)
            if d > 0:
                total[name] = total.get(name, 0.0) + d
            i += 1
    return total


def short_op_name(hlo_text: str) -> str:
    """`%fusion.3 = f32[...] fusion(...)` -> `fusion.3`."""
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")[:80]


def short_module_name(name: str) -> str:
    """`jit_stage(123456)` -> `jit_stage`."""
    return re.sub(r"\(\d+\)$", "", name)[:80]


def _top(total: dict, n: int, scale: float) -> list:
    return [[k, v * scale] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def reduce_trace(profile, marker: str, marker_host_us: float,
                 window_host_us: tuple, spans: list, top: int = 10) -> dict:
    """Device busy time, operation totals and named idle gaps inside the
    traced window.

    `marker` is the annotation the harness emitted at `marker_host_us` on
    the clock of `spans` (microseconds); it ties the trace's clock to that
    one. `spans` are the program's span records (`ts`, `dur` in
    microseconds, `tid`, `name`). Returns None where the trace holds no
    device plane or no marker.
    """
    m_ns = find_marker(profile, marker)
    planes = device_planes(profile)
    if m_ns is None or not planes:
        return None

    def to_ns(host_us: float) -> float:
        return m_ns + (host_us - marker_host_us) * 1e3

    lo, hi = to_ns(window_host_us[0]), to_ns(window_host_us[1])
    devices = []
    op_total: dict = {}
    first_busy = None
    for dev_id, plane in planes:
        ops = _events(plane, OPS_LINE)
        busy = merge(ops, lo, hi)
        if first_busy is None:
            first_busy = busy
        mods = _events(plane, MODULES_LINE)
        mod_segs = self_segments(
            [(s, e, short_module_name(n)) for s, e, n in mods], lo, hi,
            outside="(no module)")
        mod_starts = [s for s, _, _ in mod_segs]
        for s, e, name in self_segments(
                [(s, e, short_op_name(n)) for s, e, n in ops], lo, hi,
                outside=""):
            if not name:
                continue
            i = max(0, bisect.bisect_right(mod_starts, s) - 1)
            mod = mod_segs[i][2] if mod_segs else "(no module)"
            key = f"{mod}/{name}"
            op_total[key] = op_total.get(key, 0.0) + (e - s)
        devices.append({"id": dev_id, "ops": len(ops),
                        "busy_s": sum(e - s for s, e in busy) / 1e9})
    # idle gaps of the first device, named on the thread that ran the job
    idle = gaps(first_busy, lo, hi)
    host = [(to_ns(sp["ts"]), to_ns(sp["ts"] + sp["dur"]), sp["name"],
             sp.get("tid")) for sp in spans]
    tid = job_thread(host, lo, hi)
    segs = self_segments([h[:3] for h in host if h[3] == tid], lo, hi)
    return {
        "devices": devices,
        "busy_s": sum(d["busy_s"] for d in devices) / len(devices),
        "window_s": (hi - lo) / 1e9,
        "device_op_events": sum(d["ops"] for d in devices),
        "device_ops": _top(op_total, top, 1e-9 / len(devices)),
        "idle_gaps": _top(overlap_by_name(idle, segs), top, 1e-9),
        "longest_idle_gap_s": max((e - s for s, e in idle), default=0.0)
        / 1e9,
    }


def job_thread(host: list, lo: float, hi: float):
    """The thread whose spans cover most of [lo, hi]: the one that ran the
    job (pool threads hold short spans)."""
    cover: dict = {}
    for s, e, _, tid in host:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            cover[tid] = max(cover.get(tid, 0.0), d)
    return max(cover, key=cover.get) if cover else None
