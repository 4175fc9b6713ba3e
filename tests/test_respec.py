"""Closed-loop re-specialization (serve/respec): background candidate
compiles on the low-priority lane, canary validation, hot-swap atomicity
at job boundaries, incumbent fallback in the tier ladder, quarantine
markers, the excprof scope-retirement satellite, and the tier-1 smoke
(synthetic zillow drift -> respec promotes -> drift clears)."""

import json
import os
import threading
import time

import tuplex_tpu
from tuplex_tpu.exec import compilequeue as CQ
from tuplex_tpu.runtime import excprof, telemetry, xferstats
from tuplex_tpu.serve import JobService, request_from_dataset
from tuplex_tpu.serve.respec import apply_overlay_to_stage

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _svc_ctx(tmp_path, **extra):
    conf = {"tuplex.scratchDir": str(tmp_path / "scratch"),
            "tuplex.partitionSize": "64KB"}
    conf.update(extra)
    return tuplex_tpu.Context(conf)


def _overlay(tenant="t", gen=1, stages=None):
    return {"gen": gen, "tenant": tenant, "salt": f"{tenant}:g{gen}",
            "anchor_rate": 0.0, "stages": stages or {}, "sig": "sigtest"}


# ---------------------------------------------------------------------------
# background compile lane
# ---------------------------------------------------------------------------

def test_background_lane_runs_on_its_own_pool():
    """A submit inside background_lane() never lands on a foreground
    pool worker (the zero-foreground-impact contract): it executes on
    the dedicated tpx-bgcompile thread and bumps background_compiles."""
    snap = CQ.snapshot()
    seen: dict = {}

    def fn(x):
        seen["thread"] = threading.current_thread().name
        return x + 1

    aval = __import__("jax").ShapeDtypeStruct((4,), "int32")
    with CQ.background_lane():
        fut = CQ.submit_compile(fn, (aval,), salt="/bgtest")
    fut.result(timeout=120)
    d = CQ.delta(snap)
    assert d["background_compiles"] == 1
    assert seen["thread"].startswith("tpx-bgcompile"), seen
    # the flag is thread-local and scoped: a submit outside the context
    # goes back to the foreground pool
    seen.clear()

    def fn2(x):
        seen["thread"] = threading.current_thread().name
        return x + 2

    CQ.submit_compile(fn2, (aval,), salt="/fgtest").result(timeout=120)
    assert seen["thread"].startswith("tpx-compile"), seen
    assert CQ.delta(snap)["background_compiles"] == 1
    assert CQ.pending_info()["background_queued"] == 0


# ---------------------------------------------------------------------------
# unified condemnation markers
# ---------------------------------------------------------------------------

def test_marker_helper_kind_scoped(tmp_path):
    base = str(tmp_path / "artifact.aot")
    p = CQ.write_marker(base, "timeout", reason="test wedge", fp="abc")
    assert p == base + ".timeout" and os.path.exists(p)
    rec = CQ.read_marker(base, "timeout")
    assert rec["kind"] == "timeout" and rec["reason"] == "test wedge"
    assert rec["platform"] and rec["fp"] == "abc"
    # absent kind: nothing
    assert CQ.read_marker(base, "nodeser") is None
    # a MISLABELED marker condemns nothing: a nodeser verdict sitting at
    # the .timeout path must not read as a timeout (different defect
    # class can never condemn a healthy artifact)
    with open(base + ".timeout", "w") as f:
        json.dump({"kind": "nodeser", "reason": "wrong class"}, f)
    assert CQ.read_marker(base, "timeout") is None
    # legacy markers (bare platform text from earlier builds) still count
    # for their own suffix
    with open(base + ".nodeser", "w") as f:
        f.write("cpu-x86")
    rec = CQ.read_marker(base, "nodeser")
    assert rec is not None and rec.get("legacy")


def test_timeout_negative_cache_still_works_via_marker(tmp_path,
                                                      monkeypatch):
    """The pre-existing `.timeout` negative-cache behavior rides the new
    helper: a written deadline verdict short-circuits later checks."""
    monkeypatch.setenv("TUPLEX_AOT_CACHE", str(tmp_path / "aot"))
    os.makedirs(str(tmp_path / "aot"), exist_ok=True)
    fp = "f" * 64
    assert not CQ._deadline_known_exceeded(fp)
    CQ._TIMEOUTS.discard(fp)
    CQ._note_deadline_exceeded(fp)
    CQ._TIMEOUTS.discard(fp)        # force the on-disk path
    assert CQ._deadline_known_exceeded(fp)
    rec = CQ.read_marker(CQ._artifact_path(fp), "timeout")
    assert rec and rec["kind"] == "timeout"


# ---------------------------------------------------------------------------
# overlay semantics on a real planned stage
# ---------------------------------------------------------------------------

def _plan_one_stage(ctx):
    from tuplex_tpu.plan.physical import plan_stages

    ds = (ctx.parallelize([(i, f"s{i}") for i in range(64)],
                          columns=["a", "s"])
          .map(lambda x: (x["a"] * 2, x["s"].upper())))
    stages = plan_stages(ds._op, ctx.options_store)
    return [s for s in stages if hasattr(s, "possible_exception_codes")][0]


def test_overlay_changes_key_widens_inventory_and_reverts(ctx):
    from tuplex_tpu.core.errors import ExceptionCode as EC

    stage = _plan_one_stage(ctx)
    k0 = stage.key()
    codes0 = set(int(c) for c in stage.possible_exception_codes())
    extra = int(EC.STOPITERATION)
    assert extra not in codes0
    ov = _overlay(stages={0: {"extra_codes": [extra]}})
    notified = []
    apply_overlay_to_stage(stage, ov, 0, notify=notified.append)
    assert stage.key() != k0, "overlay must change the stage key"
    assert stage.respec_salt == "t:g1"
    codes1 = set(int(c) for c in stage.possible_exception_codes())
    assert extra in codes1, "observed code not adopted into the inventory"
    # the widened inventory reaches the resolve plan's preallocation
    assert extra in stage.resolve_plan().codes
    # revert restores the incumbent exactly (the exec/local fallback rung)
    rev = stage._respec_revert
    for k, v in rev.items():
        setattr(stage, k, v)
    if hasattr(stage, "_resolve_plan_memo"):
        delattr(stage, "_resolve_plan_memo")
    assert stage.key() == k0
    assert set(int(c) for c in stage.possible_exception_codes()) == codes0


# ---------------------------------------------------------------------------
# incumbent fallback rung in the tier ladder
# ---------------------------------------------------------------------------

def test_tier_restart_reverts_to_incumbent_generation(tmp_path,
                                                      monkeypatch):
    from tuplex_tpu.exec import local as XL

    c = _svc_ctx(tmp_path, **{"tuplex.tpu.compileDeadlineS": "60"})
    stage = _plan_one_stage(c)
    notified = []
    apply_overlay_to_stage(stage, _overlay(), 0, notify=notified.append)
    backend = c.backend
    from tuplex_tpu.api.dataset import _source_partitions

    parts = _source_partitions(c, stage, lazy=False)
    orig = XL.LocalBackend._run_stage_tier
    tiers = []

    def fake(self, st, stream, first, inter, tier, box_output=False):
        tiers.append(tier)
        if getattr(st, "_respec_revert", None) is not None:
            # simulate the candidate generation blowing its compile
            # deadline at dispatch time
            raise XL._TierRestart("cpu", RuntimeError("candidate wedge"))
        return orig(self, st, stream, first, inter, tier, box_output)

    monkeypatch.setattr(XL.LocalBackend, "_run_stage_tier", fake)
    res = backend.execute(stage, list(parts))
    # the retry ran on the DEVICE tier of the incumbent generation, not
    # one rung down the degrade ladder — and from partition 0
    assert tiers == ["device", "device"]
    assert stage.respec_salt == "" and stage._respec_revert is None
    assert len(notified) == 1, "controller was not told about the rollback"
    assert res.metrics["rows_out"] == 64
    assert res.metrics["tier_restarts"] == 1
    c.close()


# ---------------------------------------------------------------------------
# hot-swap atomicity
# ---------------------------------------------------------------------------

def test_promotion_applies_only_to_jobs_admitted_after_swap(tmp_path):
    c = _svc_ctx(tmp_path)
    svc = JobService(c.options_store, autostart=False)
    assert svc.respec is not None
    tenant = "swappy"

    def req():
        ds = (c.parallelize([(i, f"s{i}") for i in range(256)],
                            columns=["a", "s"])
              .map(lambda x: (x["a"] + 1, x["s"].upper())))
        return request_from_dataset(ds, name="swap", tenant=tenant)

    ha = svc.submit(req())                      # admitted at gen 0
    # promotion lands while A is admitted but not yet running
    st = svc.respec._state(tenant)
    with svc.respec._lock:
        st.gen = 1
        st.overlay = _overlay(tenant, 1)
    hb = svc.submit(req())                      # admitted at gen 1
    a_salts = {s.respec_salt for s in ha._rec.runner.stages}
    b_salts = {s.respec_salt for s in hb._rec.runner.stages}
    assert a_salts == {""}, "in-flight job picked up a later promotion"
    assert b_salts == {f"{tenant}:g1"}, \
        "job admitted after the swap did not get the new generation"
    svc.start()
    assert ha.wait(300) == "done" and hb.wait(300) == "done"
    assert ha.result() == hb.result(), \
        "generations disagreed on the same input"
    svc.close()
    c.close()


def test_retry_rebuild_keeps_pinned_generation(tmp_path):
    """A retry replays the job from stage 0 under the generation PINNED
    AT ADMISSION, even when the tenant was promoted in between — one job
    never mixes plan generations across attempts."""
    from tuplex_tpu.serve.jobs import _JobRunner

    c = _svc_ctx(tmp_path)
    svc = JobService(c.options_store, autostart=False)
    tenant = "pinny"
    ds = (c.parallelize([(i,) for i in range(64)], columns=["a"])
          .map(lambda x: (x["a"] * 3,)))
    h = svc.submit(request_from_dataset(ds, name="pin", tenant=tenant))
    rec = h._rec
    assert {s.respec_salt for s in rec.runner.stages} == {""}
    # the tenant is promoted mid-job...
    st = svc.respec._state(tenant)
    with svc.respec._lock:
        st.gen = 2
        st.overlay = _overlay(tenant, 2)
    # ...but the retry rebuild stays on the pinned (admission) generation
    rec.reset_for_retry()
    rec.runner = _JobRunner(rec, svc.options, svc.default_budget)
    assert {s.respec_salt for s in rec.runner.stages} == {""}
    # a NEW job of the same tenant gets the promoted generation
    h2 = svc.submit(request_from_dataset(
        (c.parallelize([(1,)], columns=["a"])), name="pin2",
        tenant=tenant))
    assert {s.respec_salt for s in h2._rec.runner.stages} \
        == {f"{tenant}:g2"}
    svc.close()
    c.close()


# ---------------------------------------------------------------------------
# canary -> promote on a live service (forced candidate, tiny pipeline)
# ---------------------------------------------------------------------------

def test_canary_cross_checks_then_promotes(tmp_path):
    c = _svc_ctx(tmp_path)
    svc = JobService(c.options_store)
    tenant = "canary-t"

    def submit():
        ds = (c.parallelize([(i, f"v{i}") for i in range(512)],
                            columns=["a", "s"])
              .map(lambda x: (x["a"] * 2, x["s"].upper())))
        return svc.submit(request_from_dataset(ds, name="cj",
                                               tenant=tenant))

    h1 = submit()
    assert h1.wait(300) == "done"
    want = h1.result()
    # hand the controller a validated candidate awaiting canary
    st = svc.respec._state(tenant)
    cand = {"gen": 1, "state": "ready", "t_start": time.monotonic(),
            "t_trigger": time.monotonic(),
            "overlay": _overlay(tenant, 1), "sig": "cansig",
            "checks": [], "failed": None, "canary_job": None}
    with svc.respec._lock:
        st.candidate = cand
    h2 = submit()
    assert h2.wait(300) == "done"
    assert h2.result() == want, "canary job results must stay incumbent"
    rep = svc.respec.tenant_report(tenant)
    assert rep["promotions"] == 1, rep
    assert rep["generation"] == 1
    assert cand["checks"] and all(ch["ok"] for ch in cand["checks"])
    ch = cand["checks"][0]
    assert ch["rows"] == ch["rows_incumbent"]
    # post-swap jobs run the promoted generation and still agree
    h3 = submit()
    assert h3.wait(300) == "done"
    assert {s.respec_salt for s in h3._rec.runner.stages} \
        == {f"{tenant}:g1"}
    assert h3.result() == want
    # the lifecycle made it onto the exposition surface
    if telemetry.enabled():
        prom = telemetry.render_prometheus()
        assert "tuplex_serve_respec_promotions_total" in prom
        assert "tuplex_serve_respec_generation" in prom
    svc.close()
    c.close()


def test_failed_canary_quarantines_and_never_promotes(tmp_path,
                                                      monkeypatch):
    monkeypatch.setenv("TUPLEX_AOT_CACHE", str(tmp_path / "aot"))
    os.makedirs(str(tmp_path / "aot"), exist_ok=True)
    monkeypatch.setenv("TUPLEX_FAULTS", "respec:raise-canary:kind=det")
    from tuplex_tpu.runtime import faults

    faults.reset()
    try:
        c = _svc_ctx(tmp_path)
        svc = JobService(c.options_store)
        tenant = "poison-t"

        def submit():
            ds = (c.parallelize([(i,) for i in range(128)],
                                columns=["a"])
                  .map(lambda x: (x["a"] + 7,)))
            return svc.submit(request_from_dataset(ds, name="pj",
                                                   tenant=tenant))

        h1 = submit()
        assert h1.wait(300) == "done"
        want = h1.result()
        st = svc.respec._state(tenant)
        cand = {"gen": 1, "state": "ready",
                "t_start": time.monotonic(),
                "t_trigger": time.monotonic(),
                "overlay": _overlay(tenant, 1), "sig": "poisonsig",
                "checks": [], "failed": None, "canary_job": None}
        with svc.respec._lock:
            st.candidate = cand
        h2 = submit()
        assert h2.wait(300) == "done", (h2.state, h2.error)
        # the poisoned candidate never touches the job's results
        assert h2.result() == want
        rep = svc.respec.tenant_report(tenant)
        assert rep["promotions"] == 0
        assert rep["quarantines"] == 1
        assert rep["generation"] == 0, "poisoned candidate was promoted"
        assert "canary dispatch failed" in str(cand["failed"])
        # content-addressed quarantine marker with provenance
        base = svc.respec._quar_base("poisonsig")
        rec = CQ.read_marker(base, "respecquar")
        assert rec and rec["kind"] == "respecquar" \
            and rec["tenant"] == tenant
        # a later job runs the incumbent, unharmed
        h3 = submit()
        assert h3.wait(300) == "done" and h3.result() == want
        assert {s.respec_salt for s in h3._rec.runner.stages} == {""}
        svc.close()
        c.close()
    finally:
        monkeypatch.delenv("TUPLEX_FAULTS", raising=False)
        faults.reset()


# ---------------------------------------------------------------------------
# excprof satellites: scope retirement + suppression + reanchor
# ---------------------------------------------------------------------------

def test_tenant_retirement_drops_excprof_scopes(tmp_path):
    """The long-lived-serve state leak: per-tenant excprof windows died
    with the process. Now a tenant whose last retained record is evicted
    drops its drift window — bounded under a churning tenant
    population."""
    excprof.clear()
    c = _svc_ctx(tmp_path, **{"tuplex.serve.retainJobs": "3"})
    svc = JobService(c.options_store)
    n = 9
    for i in range(n):
        ds = (c.parallelize([(i, i + 1)], columns=["a", "b"])
              .map(lambda x: (x["a"] + x["b"],)))
        h = svc.submit(request_from_dataset(ds, name=f"churn{i}",
                                            tenant=f"tenant-{i}"))
        assert h.wait(300) == "done"
    live = {r.request.tenant for r in svc._records.values()}
    scopes = set(excprof.scopes())
    assert scopes <= live, \
        f"retired tenants leaked drift windows: {scopes - live}"
    assert len(scopes) <= 3
    # the respec controller state retired with them
    assert set(svc.respec._states) <= live
    svc.close()
    c.close()


def test_excprof_suppressed_and_reanchor():
    excprof.clear()
    excprof.set_scope("supp-t")
    excprof.configure(window_s=0.05, half_life_s=0.05)

    def settle():
        time.sleep(0.08)
        excprof.roll()

    try:
        with excprof.suppressed():
            excprof.note_device("stg", 100,
                                packed_codes=[3] * 50, owner=1)
        assert excprof.scope_report("supp-t")["rows"] == 0, \
            "suppressed records leaked into the tenant window"
        # real traffic: calibrate a clean anchor, then drift hard
        excprof.note_device("stg", 100, packed_codes=None, owner=1)
        settle()
        for _ in range(3):
            excprof.note_device("stg", 100, packed_codes=[3] * 60,
                                owner=1)
            settle()
        assert excprof.drift_score("supp-t") > 0.5
        # promotion adopts the live distribution as the new normal
        excprof.reanchor("supp-t")
        assert excprof.drift_score("supp-t") < 0.1
        rep = excprof.scope_report("supp-t")
        assert rep["anchor_rate"] >= 0.4, rep
        # drop_scope releases the window entirely
        assert excprof.drop_scope("supp-t") is not None
        assert "supp-t" not in excprof.scopes()
        assert excprof.drop_scope("supp-t") is None
    finally:
        excprof.set_scope(None)
        excprof.configure(window_s=10.0, half_life_s=30.0)
        excprof.clear()


# ---------------------------------------------------------------------------
# crash-recovery telemetry satellite
# ---------------------------------------------------------------------------

def test_recovery_counters_and_healthz_detail(tmp_path):
    from tuplex_tpu.serve import client as WC

    root = str(tmp_path / "root")
    os.makedirs(os.path.join(root, "inbox"), exist_ok=True)
    c = _svc_ctx(tmp_path)
    ds = (c.parallelize([(i,) for i in range(32)], columns=["a"])
          .map(lambda x: (x["a"] * 5,)))
    req = request_from_dataset(ds, name="recov", tenant="rt",
                               scratch_dir=str(tmp_path / "stage"))
    jid = WC.submit(root, req)
    # forge the previous process's death: journaled admitted, no response
    WC._write_journal(os.path.join(root, "inbox", jid), "admitted")
    before = xferstats.counters().get("serve_recovered_jobs", 0)
    svc = JobService(c.options_store)
    try:
        served = [0]
        t = threading.Thread(
            target=lambda: served.__setitem__(
                0, WC.service_loop(root, service=svc, max_idle_s=2.0)),
            daemon=True)
        t.start()
        resp = WC.fetch(root, jid, timeout=300)
        assert resp["ok"] and resp["rows"] == [i * 5 for i in range(32)]
        open(os.path.join(root, "STOP"), "w").close()
        t.join(60)
        assert xferstats.counters().get("serve_recovered_jobs", 0) \
            == before + 1
        j = WC._read_journal(os.path.join(root, "inbox", jid))
        assert j.get("requeues", 0) == 1
        if telemetry.enabled():
            h = telemetry.health()
            chk = h["checks"].get("serve_recovery")
            assert chk and "1 in-flight job(s) requeued" in chk["detail"]
            prom = telemetry.render_prometheus()
            assert "tuplex_serve_recovered_jobs_total" in prom
    finally:
        svc.close()
        c.close()


# ---------------------------------------------------------------------------
# tier-1 smoke: synthetic zillow drift -> respec promotes -> drift clears
# ---------------------------------------------------------------------------

def test_respec_smoke_closed_loop():
    import sys

    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import respec_smoke
    finally:
        sys.path.pop(0)
    excprof.clear()
    assert respec_smoke.main(["--rows", "120", "--window", "0.25"]) == 0
    excprof.clear()
