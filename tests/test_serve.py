"""Job-service runtime (serve/): concurrent multi-tenant pipelines on one
warm device — admission backpressure, deficit-weighted fair scheduling,
shared compile plane with per-job telemetry/memory isolation, the
scratch-dir wire protocol, and the packed-wire AOT prewarm satellite."""

import os
import shutil
import subprocess
import sys
import threading

import pytest

import tuplex_tpu
from tuplex_tpu.exec import compilequeue as CQ
from tuplex_tpu.serve import (JobRejected, JobService,
                              request_from_dataset)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _svc_ctx(tmp_path, **extra):
    conf = {"tuplex.scratchDir": str(tmp_path / "scratch"),
            "tuplex.partitionSize": "64KB"}
    conf.update(extra)
    return tuplex_tpu.Context(conf)


# ---------------------------------------------------------------------------
# basics
# ---------------------------------------------------------------------------

def test_submit_collect_roundtrip(tmp_path):
    c = _svc_ctx(tmp_path)
    ds = (c.parallelize([(i, f"s{i}") for i in range(2000)],
                        columns=["a", "s"])
          .map(lambda x: (x["a"] * 2, x["s"].upper())))
    h = c.submit(ds, name="t1", tenant="alice")
    rows = h.result(timeout=300)
    assert rows == [(i * 2, f"S{i}") for i in range(2000)]
    assert h.state == "done"
    # the job compiled and its metrics are its own
    m = h.metrics.as_dict()
    assert m["rows_out"] == 2000
    assert m["stages"][0]["fast_path_s"] > 0, "stage did not compile"
    # per-job counter family recorded under the job's scope
    assert h.counters(), "no scoped counters for the job"
    c.close()


def test_failed_job_reports_error_service_survives(tmp_path):
    c = _svc_ctx(tmp_path)
    svc = c.job_service()
    # a stage that cannot execute -> the runner's first step explodes
    req = request_from_dataset(
        c.parallelize([1, 2, 3]).map(lambda x: x + 1), name="doomed")
    req.stages.append({"live": "not-a-stage"})
    h = svc.submit(req)
    assert h.wait(120) == "failed"
    assert h.error
    with pytest.raises(Exception):
        h.result(timeout=5)
    # the service is still alive and serves the next job
    h2 = c.submit(c.parallelize([1, 2, 3]).map(lambda x: x * 10))
    assert h2.result(timeout=300) == [10, 20, 30]
    c.close()


# ---------------------------------------------------------------------------
# acceptance: N=4 concurrent isomorphic zillow-class jobs, one warm backend
# ---------------------------------------------------------------------------

def test_four_isomorphic_zillow_jobs_share_one_compile_set(tmp_path):
    from tuplex_tpu.models import zillow
    from tuplex_tpu.runtime import tracing

    csv0 = str(tmp_path / "z0.csv")
    # 400 rows / seed 7 / default partitioning: the EXACT avals
    # scripts/serve_smoke.py dispatches, so this test and the smoke share
    # one AOT disk-cache compile set across tier-1 runs
    zillow.generate_csv(csv0, 400, seed=7)
    csvs = [csv0]
    for i in range(1, 4):
        p = str(tmp_path / f"z{i}.csv")
        shutil.copy(csv0, p)
        csvs.append(p)
    want = zillow.run_reference_python(csv0)

    was_on = tracing.enabled()
    tracing.enable(True)
    try:
        c = tuplex_tpu.Context(
            {"tuplex.scratchDir": str(tmp_path / "scratch")})
        svc = c.job_service()
        # baseline: one job alone (its compiles may be 0 on a warm AOT
        # disk cache — the bound below holds either way)
        snap = CQ.snapshot()
        h0 = svc.submit(request_from_dataset(
            zillow.build_pipeline(c.csv(csvs[0])), name="baseline",
            tenant="t0"))
        assert h0.wait(600) == "done", (h0.state, h0.error)
        single = CQ.delta(snap)["stage_compiles"]

        snap = CQ.snapshot()
        handles = [svc.submit(request_from_dataset(
            zillow.build_pipeline(c.csv(csvs[i])), name=f"j{i}",
            tenant=f"t{i}")) for i in range(4)]
        for h in handles:
            assert h.wait(600) == "done", (h.name, h.state, h.error)
            assert h.result() == want
        total = CQ.delta(snap)["stage_compiles"]
        # the acceptance bound: 4 concurrent isomorphic jobs cost at most
        # one job's compile set + 1 (here the baseline already built the
        # set, so the concurrent batch must be all cache hits)
        assert total <= single + 1, (total, single)

        # per-job Metrics isolated: each job's metrics count ITS rows only
        for h in handles:
            assert h.metrics.totalRowsOut() == len(want), h.name
        # per-job trace streams isolated: every span in a job's stream is
        # tagged with that job, streams pairwise disjoint
        streams = {h.id: h.trace_events() for h in handles}
        for h in handles:
            assert streams[h.id], f"{h.name}: empty stream"
            assert all(e.get("stream") == h.id for e in streams[h.id])
            assert any(e["name"] == "stage:execute"
                       for e in streams[h.id]), h.name
        keys = {jid: {(e["ts"], e["tid"], e["name"]) for e in evs}
                for jid, evs in streams.items()}
        ids = list(keys)
        for i in range(len(ids)):
            for j in range(i + 1, len(ids)):
                assert not (keys[ids[i]] & keys[ids[j]])
        # per-job counter families isolated and populated
        fams = [h.counters() for h in handles]
        assert all(f for f in fams)
        c.close()
    finally:
        tracing.enable(was_on)


# ---------------------------------------------------------------------------
# acceptance: fairness — a short job is not serialized behind a long one
# ---------------------------------------------------------------------------

def test_fairness_short_job_completes_before_long(tmp_path):
    c = _svc_ctx(tmp_path)
    svc = JobService(c.options_store, autostart=False)
    long_ds = (c.parallelize(list(range(30000)), columns=["v"])
               .map(lambda x: x["v"] % 977)
               .unique()
               .map(lambda x: x + 1)
               .unique())
    short_ds = c.parallelize(list(range(50)), columns=["v"]) \
        .map(lambda x: x["v"] + 5)
    hl = svc.submit(request_from_dataset(long_ds, name="long",
                                         tenant="big"))
    hs = svc.submit(request_from_dataset(short_ds, name="short",
                                         tenant="small"))
    svc.start()
    assert hs.wait(600) == "done", (hs.state, hs.error)
    assert hl.wait(600) == "done", (hl.state, hl.error)
    # round-robin at stage granularity: the short (1-stage) job finishes
    # within its first scheduling cycle — BEFORE the long job's 4-stage
    # list drains, even though the long job was admitted first
    assert hs.stats["finished_turn"] < hl.stats["finished_turn"], \
        (hs.stats, hl.stats)
    assert hs.stats["finished_turn"] <= 2 + 1, hs.stats
    assert sorted(hs.result()) == [v + 5 for v in range(50)]
    assert len(hl.result()) == 977
    svc.close()
    c.close()


# ---------------------------------------------------------------------------
# acceptance: per-job memory budget — spill/degrade, or clear rejection
# ---------------------------------------------------------------------------

def test_memory_budget_spills_instead_of_ooming(tmp_path):
    c = _svc_ctx(tmp_path)
    data = [(i, "x" * 200) for i in range(20000)]
    ds = c.parallelize(data, columns=["a", "s"]) \
        .map(lambda x: (x["a"], x["s"]))
    h = c.submit(ds, name="spill", tenant="mem", memory_budget="128KB")
    rows = h.result(timeout=600)
    assert len(rows) == 20000
    # the tiny budget forced the job's OWN MemoryManager to spill: the
    # degrade path, not an OOM of the shared process
    mm = h._rec.runner.mm_metrics()
    assert mm["swap_out"] > 0, mm
    assert h.counters().get("spill_bytes", 0) > 0, h.counters()
    c.close()


def test_budget_above_cap_rejected_at_admission(tmp_path):
    c = _svc_ctx(tmp_path, **{"tuplex.serve.maxJobMemory": "1MB"})
    ds = c.parallelize([(1,)], columns=["a"]).map(lambda x: x["a"])
    with pytest.raises(JobRejected) as ei:
        c.submit(ds, memory_budget="64MB")
    assert "memory budget" in str(ei.value)
    assert "maxJobMemory" in str(ei.value)
    c.close()


# ---------------------------------------------------------------------------
# admission queue: bounded, backpressure, clear rejection
# ---------------------------------------------------------------------------

def test_admission_queue_backpressure(tmp_path):
    c = _svc_ctx(tmp_path, **{"tuplex.serve.queueDepth": 1,
                              "tuplex.serve.admissionTimeoutS": "0.2"})
    svc = JobService(c.options_store, autostart=False)
    ds = c.parallelize(list(range(10)), columns=["v"]) \
        .map(lambda x: x["v"])
    svc.submit(request_from_dataset(ds, name="q1"))
    with pytest.raises(JobRejected) as ei:
        svc.submit(request_from_dataset(ds, name="q2"))
    assert "queue full" in str(ei.value)
    svc.close()
    c.close()


def test_tenant_weights_parse_and_apply(tmp_path):
    c = _svc_ctx(tmp_path,
                 **{"tuplex.serve.tenantWeights": "gold:3,bronze:1"})
    svc = JobService(c.options_store, autostart=False)
    ds = c.parallelize(list(range(5)), columns=["v"]).map(lambda x: x["v"])
    hg = svc.submit(request_from_dataset(ds, name="g", tenant="gold"))
    hb = svc.submit(request_from_dataset(ds, name="b", tenant="bronze"))
    assert hg._rec.weight == 3 and hb._rec.weight == 1
    svc.start()
    assert hg.wait(300) == "done" and hb.wait(300) == "done"
    svc.close()
    c.close()


def test_terminal_records_bounded_and_counters_released(tmp_path):
    # a long-lived service must not grow per job served: terminal records
    # beyond retainJobs drop from the index (held handles stay valid) and
    # each job's scoped counter family is snapshotted then released
    from tuplex_tpu.runtime import xferstats

    c = _svc_ctx(tmp_path, **{"tuplex.serve.retainJobs": 1})
    svc = c.job_service()
    ds = c.parallelize(list(range(50)), columns=["v"]).map(lambda x: x["v"])
    h1 = svc.submit(request_from_dataset(ds, name="j1"))
    assert h1.wait(300) == "done"
    h2 = svc.submit(request_from_dataset(ds, name="j2"))
    assert h2.wait(300) == "done"
    assert h2.id in svc._records
    assert h1.id not in svc._records          # evicted past retainJobs
    assert h1.result() == list(range(50))     # the held handle still works
    # the live registry released both jobs' scopes; counters survive on
    # the record snapshot
    assert h1.id not in xferstats.scopes()
    assert h2.id not in xferstats.scopes()
    assert h2.counters() == h2._rec.final_counters
    c.close()


# ---------------------------------------------------------------------------
# wire protocol (serve/client.py) + CLI
# ---------------------------------------------------------------------------

def test_wire_protocol_submit_poll_fetch(tmp_path):
    from tuplex_tpu.serve import client as sc

    csv = tmp_path / "in.csv"
    with open(csv, "w") as fp:
        fp.write("a,b\n")
        for i in range(500):
            fp.write(f"{i},{i % 7}\n")
    c = _svc_ctx(tmp_path)
    req = request_from_dataset(c.csv(str(csv)).map(lambda x: x["a"] + x["b"]),
                               name="wire", tenant="w")
    assert req.wire_safe()
    root = str(tmp_path / "svcroot")
    svc = JobService(c.options_store)
    t = threading.Thread(target=sc.service_loop, args=(root,),
                         kwargs={"service": svc, "max_idle_s": 60},
                         daemon=True)
    t.start()
    jid = sc.submit(root, req)
    resp = sc.fetch(root, jid, timeout=300)
    assert resp["ok"], resp
    assert resp["rows"] == [i + i % 7 for i in range(500)]
    assert resp["metrics"]["rows_out"] == 500
    assert sc.poll(root, jid).get("state") == "done"
    open(os.path.join(root, "STOP"), "w").close()
    t.join(15)
    svc.close()
    c.close()


def test_wire_rejects_live_stage_requests(tmp_path):
    from tuplex_tpu.serve import client as sc

    c = _svc_ctx(tmp_path)
    # aggregates ride live (driver tier) — not wire-shippable
    agg_req = request_from_dataset(
        c.parallelize(list(range(100)), columns=["v"])
        .map(lambda x: x["v"] % 3).unique(), name="agg")
    assert not agg_req.wire_safe()
    with pytest.raises(JobRejected):
        sc.submit(str(tmp_path / "root"), agg_req)
    # a rejected request's staged input parts are released with it
    ds = c.parallelize(list(range(50)), columns=["v"]) \
        .map(lambda x: x["v"] + 1)
    req = request_from_dataset(ds, name="staged")
    req.stages.append({"live": "not-wire-safe"})
    indirs = [e["indir"] for e in req.stages
              if isinstance(e, dict) and e.get("indir")]
    assert indirs and all(os.path.isdir(p) for p in indirs)
    with pytest.raises(JobRejected):
        sc.submit(str(tmp_path / "root"), req)
    assert not any(os.path.exists(p) for p in indirs)
    c.close()


def test_wire_loop_retries_queue_full_without_blocking(tmp_path):
    # depth-1 service: the second request waits in the poll loop (never
    # blocking it) and admits once the first job's slot frees
    from tuplex_tpu.serve import client as sc

    c = _svc_ctx(tmp_path, **{"tuplex.serve.queueDepth": 1,
                              "tuplex.serve.admissionTimeoutS": "30"})
    csv = tmp_path / "in.csv"
    with open(csv, "w") as fp:
        fp.write("a\n")
        for i in range(300):
            fp.write(f"{i}\n")
    root = str(tmp_path / "root")
    svc = JobService(c.options_store)
    t = threading.Thread(target=sc.service_loop, args=(root,),
                         kwargs={"service": svc, "max_idle_s": 60},
                         daemon=True)
    t.start()
    wire_ds = c.csv(str(csv)).map(lambda x: x["a"] + 1)
    jids = [sc.submit(root, request_from_dataset(wire_ds, name=f"q{i}"))
            for i in range(3)]
    for jid in jids:
        resp = sc.fetch(root, jid, timeout=300)
        assert resp["ok"], resp
        assert resp["rows"] == [i + 1 for i in range(300)]
        # per-tenant metrics embed the job's OWN counter family, not the
        # process-global registry
        assert resp["metrics"]["counters"] == resp["counters"]
    open(os.path.join(root, "STOP"), "w").close()
    t.join(15)
    svc.close()
    c.close()


def test_serve_cli_starts_and_stops(tmp_path):
    # argparse wiring + loop shutdown: STOP pre-created -> immediate exit
    root = tmp_path / "cliroot"
    root.mkdir()
    open(root / "STOP", "w").close()
    out = subprocess.run(
        [sys.executable, "-m", "tuplex_tpu", "serve", str(root)],
        capture_output=True, text=True, timeout=240,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-800:]
    assert "0 job(s) served" in out.stdout


# ---------------------------------------------------------------------------
# satellite: packed-wire AOT prewarm (predicted buffer spec from avals)
# ---------------------------------------------------------------------------

def test_packed_stage_prewarm_hits_at_dispatch(tmp_path, monkeypatch):
    monkeypatch.setenv("TUPLEX_PACK_TRANSFERS", "1")
    from tuplex_tpu.api.dataset import _source_partitions
    from tuplex_tpu.compiler import stagefn as SF
    from tuplex_tpu.plan.physical import plan_stages
    from tuplex_tpu.runtime import columns as C
    from tuplex_tpu.runtime.packing import PackedOuts, PackedStageFn

    c = _svc_ctx(tmp_path)
    ds = (c.parallelize([(i, f"str{i}") for i in range(4000)],
                        columns=["a", "s"])
          .map(lambda x: (x["a"] * 3, x["s"].upper())))
    st = plan_stages(ds._op, c.options_store)[0]
    part = _source_partitions(c, st, lazy=False)[0]
    avals = SF.partition_avals(part, "q8")
    pfn = PackedStageFn(st.build_device_fn(part.schema), donate=False,
                        tag=st.key(), n_ops=len(st.ops))
    fut = pfn.warm(avals)
    assert fut is not None
    fut.result(timeout=300)     # the predicted-spec compile completed
    # the REAL dispatch must find the prewarmed executable: zero new
    # compiles, an in-process dedup hit, correct packed outputs
    snap = CQ.snapshot()
    outs = pfn(C.stage_partition(part, "q8").arrays)
    assert isinstance(outs, PackedOuts)
    host = outs.to_host()
    d = CQ.delta(snap)
    assert d["stage_compiles"] == 0, d
    assert d["dedup_hits"] >= 1, d
    assert "#err" in host
    c.close()


def test_precompile_driver_covers_packed_stages(tmp_path, monkeypatch):
    # the plan-level AOT walk (LocalBackend._precompile_driver) must now
    # submit a compile for packed-wire stages instead of skipping them
    monkeypatch.setenv("TUPLEX_PACK_TRANSFERS", "1")
    from tuplex_tpu.api.dataset import _source_partitions
    from tuplex_tpu.plan.physical import plan_stages

    c = _svc_ctx(tmp_path)
    ds = (c.parallelize([(i, f"v{i}") for i in range(4000)],
                        columns=["a", "s"])
          .map(lambda x: (x["a"] + 1, x["s"])))
    st = plan_stages(ds._op, c.options_store)[0]
    parts = _source_partitions(c, st, lazy=False)
    # every distinct bucket of the partitions in hand: the short tail
    # stages into a smaller wire buffer than the four full partitions
    futs = c.backend._precompile_driver([st], parts)
    assert futs, "no prewarm future submitted for the packed stage"
    for f in futs:
        f.result(timeout=300)
    snap = CQ.snapshot()
    got = (ds.collect(), CQ.delta(snap))
    assert got[0][0] == (1, "v0")
    assert got[1]["stage_compiles"] == 0, got[1]
    c.close()


# ---------------------------------------------------------------------------
# dashboard rows for serve jobs
# ---------------------------------------------------------------------------

def test_serve_jobs_render_in_history(tmp_path):
    import json

    c = _svc_ctx(tmp_path, **{"tuplex.webui.enable": True,
                              "tuplex.logDir": str(tmp_path)})
    ds = c.parallelize(list(range(100)), columns=["v"]) \
        .map(lambda x: x["v"] * 2)
    h = c.submit(ds, name="dash", tenant="ui")
    assert h.wait(300) == "done"
    recs = [json.loads(ln)
            for ln in open(tmp_path / "tuplex_history.jsonl")]
    mine = [r for r in recs if r.get("job") == h.id]
    evs = {r["event"] for r in mine}
    assert "job_start" in evs and "job_done" in evs, evs
    start = next(r for r in mine if r["event"] == "job_start")
    assert start["tenant"] == "ui" and start["action"] == "serve:dash"
    done = next(r for r in mine if r["event"] == "job_done")
    assert done["rows"] == 100
    from tuplex_tpu.history.recorder import render_report

    out = render_report(str(tmp_path), str(tmp_path / "report.html"))
    assert h.id in open(out).read()
    c.close()


# ---------------------------------------------------------------------------
# tier-1 wiring of the CI smoke (like scripts/trace_smoke.py)
# ---------------------------------------------------------------------------

def test_serve_smoke_zillow():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "serve_smoke.py")],
        capture_output=True, text=True, timeout=580,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "serve-smoke OK" in out.stdout
