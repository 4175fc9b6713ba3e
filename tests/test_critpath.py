"""Latency-budget plane (runtime/critpath): priority-sweep critical-path
attribution (exclusive buckets, no double-count), inline-vs-pool compile
thread awareness, degraded-input tolerance (ring wrap, cross-thread
complete() spans, orphans), per-tenant EWMA baselines + slow-job blame,
SLO attainment / multi-window burn with the `slo` health check, the
connected-tree span-embed truncation (history/recorder), Prometheus /
dashboard / whyslow exposition parity, the kill-switch zero-alloc
contract, the resolve-fault three-way blame agreement and the zillow
smoke (scripts/critpath_smoke.py) tier-1 wiring."""

import io
import json
import os
import subprocess
import sys
import time

import pytest

from tuplex_tpu.runtime import critpath as CP
from tuplex_tpu.runtime import telemetry as T

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_critpath():
    CP.clear()
    CP.enable(True)
    CP.configure(half_life_s=120.0, slow_factor=1.5, slo_ms=0.0,
                 tenant_slos={}, burn_window_s=60.0, slo_target=0.9,
                 min_base_jobs=3)
    yield
    CP.clear()
    CP.enable(True)
    CP.configure(half_life_s=120.0, slow_factor=1.5, slo_ms=0.0,
                 tenant_slos={}, burn_window_s=60.0, slo_target=0.9,
                 min_base_jobs=3)


def _sp(name, ts, dur, tid=1, depth=0, cat="exec"):
    return {"name": name, "ph": "X", "ts": float(ts), "dur": float(dur),
            "tid": tid, "depth": depth, "cat": cat}


# ---------------------------------------------------------------------------
# the sweep: exclusive attribution, priorities, the honest remainder
# ---------------------------------------------------------------------------

def test_buckets_are_exclusive_and_sum_to_wall():
    evts = [
        _sp("job", 0, 1000, depth=0),
        _sp("partition:dispatch", 100, 500, depth=1),
        _sp("resolve:interpreter", 650, 150, depth=1),
        _sp("partition:merge", 850, 50, depth=1),
    ]
    r = CP.analyze_events(evts, wall_s=0.001, t0_us=0.0, t1_us=1000.0)
    assert abs(sum(r["buckets"].values()) - r["wall_s"]) < 1e-9
    assert r["buckets"]["device"] == pytest.approx(500e-6)
    assert r["buckets"]["resolve_interpreter"] == pytest.approx(150e-6)
    assert r["buckets"]["merge"] == pytest.approx(50e-6)
    # the job wrapper owns only the slices nothing narrower covers
    assert r["buckets"]["scheduler_other"] == pytest.approx(300e-6)
    assert r["buckets"]["unattributed"] == 0.0
    assert r["coverage_frac"] == 1.0


def test_narrow_pass_beats_containing_wrapper():
    evts = [
        _sp("partition:dispatch", 0, 1000, depth=0),
        _sp("h2d:leaf-stage", 100, 200, depth=1),
        _sp("d2h:packed-fetch", 700, 100, depth=1),
    ]
    r = CP.analyze_events(evts, t0_us=0.0, t1_us=1000.0)
    assert r["buckets"]["h2d"] == pytest.approx(200e-6)
    assert r["buckets"]["d2h"] == pytest.approx(100e-6)
    assert r["buckets"]["device"] == pytest.approx(700e-6)


def test_pool_compile_overlapping_device_is_free():
    """A pool thread (tid that runs ONLY compile spans) compiling while
    the device executes is overlap working as designed — the device owns
    the slice; the compile appears nowhere in the vector."""
    evts = [
        _sp("partition:dispatch", 0, 1000, tid=1),
        _sp("compile:xla", 100, 800, tid=9),     # pool: overlapped
    ]
    r = CP.analyze_events(evts, t0_us=0.0, t1_us=1000.0)
    assert r["buckets"]["device"] == pytest.approx(1000e-6)
    assert r["buckets"]["compile_xla"] == 0.0


def test_inline_compile_on_job_thread_beats_device():
    """The same compile span on the JOB thread (a tid that also runs
    non-compile spans) is a blocking inline compile: it must win the
    slice — and keep the trace/lower/xla split."""
    evts = [
        _sp("partition:dispatch", 0, 1000, tid=1),
        _sp("compile:trace", 100, 100, tid=1, depth=1),
        _sp("compile:xla", 200, 700, tid=1, depth=1),
    ]
    r = CP.analyze_events(evts, t0_us=0.0, t1_us=1000.0)
    assert r["buckets"]["compile_trace"] == pytest.approx(100e-6)
    assert r["buckets"]["compile_xla"] == pytest.approx(700e-6)
    assert r["buckets"]["device"] == pytest.approx(200e-6)


def test_queue_wait_blocked_on_pool_reports_as_compile():
    """compile:queue-wait exists only while the caller BLOCKS on the
    pool: those slices fold into compile_xla even though the pool's own
    spans sit on another tid."""
    evts = [
        _sp("partition:dispatch", 0, 1000, tid=1),
        _sp("compile:queue-wait", 50, 800, tid=1, depth=1),
        _sp("compile:xla", 60, 780, tid=9),
    ]
    r = CP.analyze_events(evts, t0_us=0.0, t1_us=1000.0)
    assert r["buckets"]["compile_xla"] == pytest.approx(800e-6)
    assert r["buckets"]["device"] == pytest.approx(200e-6)


def test_the_collect_side_wait_for_the_chip_reports_as_device():
    """`dispatch:device-wait` heads a partition's collect (a d2h-plane
    wrapper) since PR 36: the thread is blocked on the chip there, so the
    slice is the device's, and the fetch after it stays d2h's."""
    evts = [
        _sp("partition:dispatch", 0, 100, tid=1),
        _sp("partition:collect-fast", 100, 900, tid=1),
        _sp("dispatch:device-wait", 110, 600, tid=1, depth=1),
        _sp("d2h:packed-fetch", 720, 200, tid=1, depth=1),
    ]
    r = CP.analyze_events(evts, t0_us=0.0, t1_us=1000.0)
    assert r["buckets"]["device"] == pytest.approx(700e-6)
    assert r["buckets"]["d2h"] == pytest.approx(300e-6)
    assert "device_wait" not in r["buckets"]
    assert {seg[2] for seg in r["path"]} <= set(CP.BUCKETS)


def test_queue_waits_ride_as_scalars_and_unattributed_absorbs_gap():
    evts = [_sp("job", 0, 400, depth=0)]
    r = CP.analyze_events(evts, wall_s=0.002, queued_s=0.0005,
                          stage_queue_s=0.0003, t0_us=0.0, t1_us=400.0)
    assert r["buckets"]["admission_wait"] == pytest.approx(0.0005)
    assert r["buckets"]["queue_wait"] == pytest.approx(0.0003)
    assert r["buckets"]["scheduler_other"] == pytest.approx(400e-6)
    # wall 2ms - 0.8ms waits - 0.4ms spans = 0.8ms unattributed
    assert r["buckets"]["unattributed"] == pytest.approx(0.0008)
    assert abs(sum(r["buckets"].values()) - r["wall_s"]) < 1e-9
    assert r["unattributed_frac"] == pytest.approx(0.4)


def test_wall_clamped_up_to_covered_never_over_100pct():
    evts = [_sp("partition:dispatch", 0, 5000)]
    r = CP.analyze_events(evts, wall_s=0.001, t0_us=0.0, t1_us=5000.0)
    assert r["wall_s"] >= 0.005 - 1e-9
    assert r["buckets"]["unattributed"] == 0.0
    assert r["coverage_frac"] <= 1.0


def test_critical_path_segments_cover_window_in_order():
    evts = [
        _sp("job", 0, 300, depth=0),
        _sp("h2d:packed-upload", 50, 100, depth=1),
    ]
    r = CP.analyze_events(evts, t0_us=0.0, t1_us=300.0)
    path = r["path"]
    assert [p[2] for p in path] == \
        ["scheduler_other", "h2d", "scheduler_other"]
    assert path[0][0] == 0.0 and sum(p[1] for p in path) == \
        pytest.approx(300.0)


# ---------------------------------------------------------------------------
# degraded inputs: never crash, never double-count
# ---------------------------------------------------------------------------

def test_orphaned_child_degrades_to_coarse_bars():
    """depth>0 span whose parent was dropped (ring wrap): still
    attributed, flagged degraded, buckets still sum to wall."""
    evts = [_sp("resolve:general", 100, 200, depth=3)]
    r = CP.analyze_events(evts, wall_s=0.001, t0_us=0.0, t1_us=1000.0)
    assert r["degraded"] and r["n_orphans"] == 1
    assert r["buckets"]["resolve_general"] == pytest.approx(200e-6)
    assert abs(sum(r["buckets"].values()) - r["wall_s"]) < 1e-9


def test_cross_thread_complete_straddle_detected():
    """A complete() span stamped from another thread can straddle its
    neighbors instead of nesting — detection flags it, attribution
    holds (no slice counted twice)."""
    evts = [
        _sp("partition:dispatch", 0, 500, tid=1),
        _sp("d2h:device-result", 400, 300, tid=1, depth=1),  # straddles
    ]
    r = CP.analyze_events(evts, t0_us=0.0, t1_us=700.0)
    assert r["degraded"] and r["n_orphans"] >= 1
    assert r["buckets"]["device"] == pytest.approx(400e-6)
    assert r["buckets"]["d2h"] == pytest.approx(300e-6)
    assert abs(sum(r["buckets"].values()) - r["wall_s"]) < 1e-9


def test_ring_wrap_floor_still_analyzable(monkeypatch):
    """A wrapped tracing ring loses leading spans (TUPLEX_TRACE_BUFFER
    bounds the deque); the sweep must survive on the surviving tail with
    unattributed absorbing the missing head."""
    from collections import deque

    from tuplex_tpu.runtime import tracing

    monkeypatch.setattr(tracing, "_events", deque(maxlen=16))
    tracing.enable(True)
    try:
        with tracing.span("job", "exec"):
            for i in range(200):
                with tracing.span("resolve:general", "exec"):
                    pass
        evts = tracing.events()
        assert len(evts) <= 16          # the ring wrapped
        r = CP.analyze_events(evts, wall_s=1.0)
        assert r is not None
        assert abs(sum(r["buckets"].values()) - r["wall_s"]) < 1e-6
    finally:
        tracing.enable(False)


def test_garbage_events_never_crash():
    evts = [{"name": "x"}, {"ts": "bogus", "dur": "nan?", "name": 3},
            {"name": "h2d:x", "ts": 5.0, "dur": None},
            {"name": "h2d:y", "ts": 5.0, "dur": -2.0}, {}]
    r = CP.analyze_events(evts, wall_s=0.001)
    assert r["buckets"]["unattributed"] == pytest.approx(0.001)
    assert r["n_spans"] == 0


def test_empty_events_all_unattributed():
    r = CP.analyze_events([], wall_s=0.5, queued_s=0.1)
    assert r["buckets"]["admission_wait"] == pytest.approx(0.1)
    assert r["buckets"]["unattributed"] == pytest.approx(0.4)


# ---------------------------------------------------------------------------
# EWMA baselines + slow-job blame
# ---------------------------------------------------------------------------

def _budget(wall, **buckets):
    b = {k: 0.0 for k in CP.BUCKETS}
    b.update(buckets)
    covered = sum(b.values())
    b["unattributed"] = max(0.0, wall - covered)
    return {"wall_s": wall, "buckets": b,
            "unattributed_frac": b["unattributed"] / wall if wall else 0.0}


def test_blame_names_the_bucket_that_grew():
    for i in range(3):
        CP.record_job("tA", f"j{i}", _budget(1.0, device=0.8,
                                             resolve_general=0.1))
    v = CP.record_job("tA", "slow", _budget(2.5, device=0.85,
                                            resolve_general=1.6))
    assert v["slow"] is True
    assert v["blame"] == "resolve_general"
    assert v["delta_s"] == pytest.approx(1.5, rel=0.1)
    rep = CP.tenant_report("tA")
    assert rep["slow_jobs"] == 1
    assert rep["baseline"]["device"] > 0


def test_no_blame_before_min_base_jobs():
    CP.record_job("tA", "j0", _budget(1.0, device=0.9))
    v = CP.record_job("tA", "j1", _budget(10.0, device=9.9))
    assert v["slow"] is False and v["blame"] is None


def test_tiny_jobs_never_flag_on_jitter():
    """The absolute-slack floor: microsecond jobs breach the 1.5x factor
    on noise alone — the _MIN_SLOW_S term must keep them quiet."""
    for i in range(4):
        CP.record_job("tA", f"j{i}", _budget(0.002, device=0.002))
    v = CP.record_job("tA", "j", _budget(0.004, device=0.004))
    assert v["slow"] is False


def test_failed_job_counts_against_slo_not_baseline():
    CP.configure(slo_ms=100.0)
    for i in range(3):
        CP.record_job("tA", f"j{i}", _budget(0.05, device=0.05))
    base = CP.tenant_report("tA")["baseline"]["device"]
    CP.record_job("tA", "boom", _budget(5.0, device=5.0), failed=True)
    assert CP.tenant_report("tA")["baseline"]["device"] == \
        pytest.approx(base)
    assert CP.attainment("tA") == pytest.approx(3 / 4)


def test_recent_job_budget_retained_and_bounded():
    CP.record_job("tA", "j0", _budget(1.0, device=1.0))
    rec = CP.job_budget("j0")
    assert rec["tenant"] == "tA" and rec["budget"]["wall_s"] == 1.0
    assert CP.job_budget("nope") is None


def test_tenant_registry_bounded_and_droppable():
    CP.record_job("tA", "j", _budget(1.0, device=1.0))
    assert "tA" in CP.tenants()
    CP.drop_tenant("tA")
    assert "tA" not in CP.tenants()


# ---------------------------------------------------------------------------
# SLO plane: attainment, burn, the `slo` health check
# ---------------------------------------------------------------------------

def test_slo_overrides_and_parse():
    assert CP.parse_slos("a:250, b:500") == {"a": 250.0, "b": 500.0}
    assert CP.parse_slos("garbage,,x:y") == {}
    CP.configure(slo_ms=1000.0, tenant_slos="gold:100")
    assert CP.slo_for("gold") == 100.0
    assert CP.slo_for("anyone-else") == 1000.0


def test_burn_transitions_ok_degraded_and_recovers():
    """SLO below the injected-latency tenant's p95: the `slo` check goes
    degraded within one burn window and recovers after the fault clears,
    while the unaffected tenant's attainment stays 100%."""
    CP.configure(slo_ms=50.0, burn_window_s=0.4, slo_target=0.9,
                 min_base_jobs=3)
    CP._ensure_health()
    assert T.health()["checks"]["slo"]["state"] == T.OK
    # healthy traffic on both tenants
    for i in range(3):
        CP.record_job("victim", f"v{i}", _budget(0.01, device=0.01))
        CP.record_job("bystander", f"b{i}", _budget(0.01, device=0.01))
    assert T.health()["checks"]["slo"]["state"] == T.OK
    # fault window: the victim's jobs blow through 50ms
    for i in range(4):
        CP.record_job("victim", f"s{i}",
                      _budget(0.2, resolve_interpreter=0.2))
    h = T.health()["checks"]["slo"]
    assert h["state"] in (T.DEGRADED, T.UNHEALTHY)
    assert "victim" in h["detail"] and "50" in h["detail"]
    assert CP.burn_rates("victim")["fast"] >= 1.0
    # the bystander is untouched
    assert CP.attainment("bystander") == 1.0
    assert CP.burn_rates("bystander")["fast"] == 0.0
    # fault clears: misses age out of both windows -> OK again
    time.sleep(0.45)
    for i in range(3):
        CP.record_job("victim", f"r{i}", _budget(0.01, device=0.01))
    time.sleep(2.1)                     # slow window = 5 x 0.4s
    assert T.health()["checks"]["slo"]["state"] == T.OK
    assert CP.attainment("bystander") == 1.0


def test_sustained_burn_goes_unhealthy():
    CP.configure(slo_ms=10.0, burn_window_s=30.0, slo_target=0.9)
    CP._ensure_health()
    for i in range(5):
        CP.record_job("t", f"j{i}", _budget(1.0, device=1.0))
    assert T.health()["checks"]["slo"]["state"] == T.UNHEALTHY


def test_no_slo_declared_never_degrades():
    CP.configure(slo_ms=0.0)
    CP._ensure_health()
    for i in range(5):
        CP.record_job("t", f"j{i}", _budget(9.0, device=9.0))
    assert CP.attainment("t") is None
    assert T.health()["checks"]["slo"]["state"] == T.OK


# ---------------------------------------------------------------------------
# options plumbing
# ---------------------------------------------------------------------------

def test_apply_options_wires_knobs():
    from tuplex_tpu.core.options import ContextOptions

    o = ContextOptions()
    o.set("tuplex.serve.sloMs", 750)
    o.set("tuplex.serve.tenantSlos", "gold:100,best:50")
    o.set("tuplex.serve.sloBurnWindowS", 120)
    o.set("tuplex.serve.sloTarget", 0.99)
    o.set("tuplex.tpu.critpathHalfLifeS", 60)
    o.set("tuplex.tpu.critpathSlowFactor", 2.0)
    CP.apply_options(o)
    assert CP.enabled()
    assert CP.slo_for("gold") == 100.0 and CP.slo_for("x") == 750.0
    assert CP._burn_window_s == 120.0 and CP._slo_target == 0.99
    assert CP._half_life_s == 60.0 and CP._slow_factor == 2.0


# ---------------------------------------------------------------------------
# span-embed truncation: the slice stays a connected tree
# ---------------------------------------------------------------------------

def _tree_evts(n_leaves=20):
    evts = [{"name": "job", "ts": 0.0, "dur": 1000.0, "tid": 1,
             "depth": 0}]
    for s in range(3):
        st = s * 300.0
        evts.append({"name": f"stage{s}", "ts": st, "dur": 280.0,
                     "tid": 1, "depth": 1})
        for k in range(n_leaves):
            evts.append({"name": f"leaf{s}.{k}", "ts": st + k * 10.0,
                         "dur": 5.0 + k, "tid": 1, "depth": 2})
    return evts


def test_span_slice_keeps_connected_tree():
    from tuplex_tpu.history.recorder import _span_slice

    evts = _tree_evts()
    spans, n_total, n_dropped = _span_slice(evts, 10)
    assert (n_total, n_dropped, len(spans)) == (64, 54, 10)
    names = {s["name"] for s in spans}
    # interior nodes survive by construction; every kept leaf's parent
    # is kept too — the slice reconstructs as one tree
    assert "job" in names
    for s in spans:
        if s["name"].startswith("leaf"):
            assert f"stage{s['name'][4]}" in names, s["name"]
    # kept leaves are the longest (shortest dropped first per depth)
    assert any(s["name"].endswith(".19") for s in spans)


def test_span_slice_cascades_to_interior_nodes():
    from tuplex_tpu.history.recorder import _span_slice

    spans, n_total, n_dropped = _span_slice(_tree_evts(), 2)
    assert len(spans) == 2 and n_dropped == n_total - 2
    names = [s["name"] for s in spans]
    assert "job" in names               # the root is the last survivor


def test_span_slice_drop_accounting_exact():
    from tuplex_tpu.history.recorder import _span_slice
    from tuplex_tpu.runtime import xferstats

    before = xferstats.as_dict().get("trace_spans_dropped", 0)
    _span_slice(_tree_evts(), 10)
    after = xferstats.as_dict().get("trace_spans_dropped", 0)
    assert after - before == 54


def test_span_slice_under_cap_untouched():
    from tuplex_tpu.history.recorder import _span_slice

    evts = _tree_evts(2)
    spans, n_total, n_dropped = _span_slice(evts, 400)
    assert n_dropped == 0 and len(spans) == n_total


# ---------------------------------------------------------------------------
# exposition: /metrics, dashboard panel, whyslow CLI
# ---------------------------------------------------------------------------

def test_prometheus_exposition_families():
    CP.configure(slo_ms=100.0)
    for i in range(3):
        CP.record_job("ten-a", f"j{i}",
                      _budget(0.05, device=0.04, h2d=0.01))
    text = T.render_prometheus()
    assert 'tuplex_critpath_jobs{tenant="ten-a"} 3' in text
    assert 'tuplex_critpath_budget_seconds{tenant="ten-a",' \
        'bucket="device"}' in text
    assert 'tuplex_critpath_wall_ewma_seconds{tenant="ten-a"}' in text
    assert 'tuplex_critpath_slo_ms{tenant="ten-a"} 100' in text
    assert 'tuplex_critpath_slo_attainment{tenant="ten-a"} 1' in text
    assert 'tuplex_critpath_burn_rate{tenant="ten-a",window="fast"}' \
        in text


def _fake_history(tmp_path, slow=False):
    ev = {"event": "critpath", "job": "j-1", "tenant": "tA",
          "wall_s": 0.5, "dominant": "device", "coverage_frac": 0.98,
          "unattributed_frac": 0.02, "degraded": False,
          "buckets": {"device": 0.4, "h2d": 0.05, "scheduler_other": 0.04,
                      "unattributed": 0.01},
          "baseline": {"device": 0.35, "h2d": 0.05},
          "path": [[0.0, 400000.0, "device", "partition:dispatch"],
                   [400000.0, 50000.0, "h2d", "h2d:leaf-stage"]],
          "slow": slow, "blame": "device" if slow else None,
          "delta_s": 0.1 if slow else 0.0, "slo_ms": 600.0,
          "slo_ok": True}
    spans = {"event": "spans", "job": "j-1", "n_total": 2, "n_dropped": 0,
             "spans": [{"name": "partition:dispatch", "cat": "exec",
                        "ts": 0.0, "dur": 450000.0, "tid": 1, "depth": 0},
                       {"name": "h2d:leaf-stage", "cat": "xfer",
                        "ts": 400000.0, "dur": 50000.0, "tid": 1,
                        "depth": 1}]}
    done = {"event": "job_done", "job": "j-1", "rows": 10, "wall_s": 0.5}
    p = tmp_path / "tuplex_history.jsonl"
    p.write_text("".join(json.dumps(e) + "\n" for e in (ev, spans, done)))
    return str(tmp_path)


def test_dashboard_budget_panel_and_waterfall_highlight(tmp_path):
    from tuplex_tpu.history.recorder import render_report

    d = _fake_history(tmp_path, slow=True)
    html = open(render_report(d)).read()
    assert "latency budget" in html
    assert "cptrack" in html and "cp-device" in html
    assert "SLOW — blame" in html
    # the waterfall outlines the bars the path owns
    assert "onpath" in html
    assert "critical path (outlined)" in html


def test_whyslow_cli_reads_the_same_record(tmp_path, capsys):
    from tuplex_tpu.utils.whyslow import main as ws_main

    d = _fake_history(tmp_path, slow=True)
    assert ws_main(d) == 0
    out = capsys.readouterr().out
    assert "dominant device" in out
    assert "SLOW: blame device" in out
    assert "SLO 600ms: met" in out
    assert "critical path" in out
    # numeric parity with the record the dashboard renders
    assert "400.0" in out               # device bucket ms


def test_whyslow_cli_empty_history(tmp_path, capsys):
    from tuplex_tpu.utils.whyslow import main as ws_main

    (tmp_path / "tuplex_history.jsonl").write_text(
        json.dumps({"event": "job_done", "job": "x"}) + "\n")
    assert ws_main(str(tmp_path)) == 0
    assert "no latency-budget events" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# kill switch: nothing recorded, nothing allocated
# ---------------------------------------------------------------------------

def test_disabled_records_nothing_and_allocates_nothing():
    CP.enable(False)
    assert CP.analyze_events([_sp("job", 0, 100)]) is None
    assert CP.record_job("t", "j", _budget(1.0, device=1.0)) == {}
    assert CP.tenants() == []
    import tracemalloc

    evts = [_sp("job", 0, 100)]
    tracemalloc.start()
    # burn-in INSIDE the traced window: the interpreter's one-time
    # inline-cache warmup on the two entry points lands before the
    # baseline snapshot, so only per-call growth is measured
    for _ in range(10000):
        CP.analyze_events(evts)
        CP.record_job("t", "j", None)
    before = tracemalloc.take_snapshot()
    for _ in range(10000):
        CP.analyze_events(evts)
        CP.record_job("t", "j", None)
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(s.size_diff for s in after.compare_to(before, "lineno")
                if s.size_diff > 0 and any(
                    (f.filename or "").replace(os.sep, "/")
                    .endswith("runtime/critpath.py")
                    for f in s.traceback))
    assert grown < 2048, \
        f"disabled path allocated {grown} bytes/10k calls"


def test_env_kill_switch_wins(monkeypatch):
    monkeypatch.setenv("TUPLEX_CRITPATH", "0")
    CP.enable(True)                     # option says on; env must win
    assert not CP.enabled()
    monkeypatch.delenv("TUPLEX_CRITPATH")
    CP.enable(True)
    assert CP.enabled()


# ---------------------------------------------------------------------------
# acceptance: injected resolve delay blamed by all three surfaces
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_resolve_fault_three_way_blame_agreement(tmp_path, capsys):
    """runtime/faults resolve-path delay: whyslow, the dashboard panel
    and the serve:slow-job instant must all blame the resolve bucket."""
    import tuplex_tpu
    from tuplex_tpu.models import zillow
    from tuplex_tpu.history.recorder import render_report
    from tuplex_tpu.runtime import faults, tracing
    from tuplex_tpu.serve import JobService, request_from_dataset
    from tuplex_tpu.utils.whyslow import main as ws_main

    data = str(tmp_path / "z.csv")
    # 400 rows matches the smoke: the generator's dirt rate guarantees
    # fallback rows, so the resolve:general stage (and its fault
    # checkpoint) actually runs on every job
    zillow.generate_csv(data, 400, seed=7)
    ctx = tuplex_tpu.Context({
        "tuplex.scratchDir": str(tmp_path / "scratch"),
        "tuplex.logDir": str(tmp_path),
        "tuplex.webui.enable": True,
        "tuplex.tpu.trace": True,
        "tuplex.tpu.critpathSlowFactor": 1.5,
        # 1s half-life: the baseline converges to the warm steady state
        # within the 4 calibration jobs even when job 0 pays a cold
        # ~100s XLA compile (at the 120s default that outlier would
        # dominate the EWMA for minutes)
        "tuplex.tpu.critpathHalfLifeS": 1,
    })
    svc = JobService(ctx.options_store, recorder=ctx.recorder)
    try:
        def run(name):
            h = svc.submit(request_from_dataset(
                zillow.build_pipeline(ctx.csv(data)), name=name,
                tenant="victim"))
            assert h.wait(1200) == "done", (name, h.state, h.error)
            return h

        for i in range(4):              # build the baseline (warm + 3)
            run(f"base{i}")
        os.environ["TUPLEX_FAULTS"] = "resolve:hang-general:delay=5.0:n=1"
        faults.reset()
        try:
            h = run("hit")
        finally:
            os.environ.pop("TUPLEX_FAULTS", None)
            faults.reset()
        lb = h.latency_budget()
        # surface 0: the budget itself
        assert lb["buckets"]["resolve_general"] >= 4.5, lb["buckets"]
        # surface 1: the serve:slow-job instant blames resolve
        inst = [e for e in tracing.events()
                if e.get("name") == "serve:slow-job"]
        assert inst, "no serve:slow-job instant"
        assert inst[-1]["args"]["blame"] == "resolve_general", inst[-1]
        # surface 2: whyslow blames resolve
        assert ws_main(str(tmp_path), job=h.id) == 0
        out = capsys.readouterr().out
        assert "SLOW: blame resolve_general" in out, out[:1200]
        # surface 3: the dashboard panel blames resolve
        html = open(render_report(str(tmp_path))).read()
        assert "SLOW — blame resolve_general" in html
    finally:
        svc.close()
        ctx.close()


# ---------------------------------------------------------------------------
# tier-1 wiring of the zillow smoke (like scripts/excprof_smoke.py)
# ---------------------------------------------------------------------------

def test_critpath_smoke_zillow():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "critpath_smoke.py")],
        capture_output=True, text=True, timeout=580,
        env={**{k: v for k, v in os.environ.items()
                if k != "TUPLEX_CRITPATH"}, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "critpath-smoke OK" in out.stdout
