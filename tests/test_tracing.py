"""Structured tracing (runtime/tracing), the tagged counter registry
(runtime/xferstats), and their surfaces: Chrome export schema, recorder
waterfall/lint rendering, the history->trace replay, the compile-queue
_CpuJit routing, and the zillow trace smoke (scripts/trace_smoke.py)."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from tuplex_tpu.runtime import tracing, xferstats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def trace_on():
    """Enable tracing for one test and restore the disabled default
    (tracing is process-global — leaked state would couple tests)."""
    tracing.clear()
    tracing.enable(True)
    yield
    tracing.enable(False)
    tracing.clear()


# ===========================================================================
# span core
# ===========================================================================

def test_span_nesting_depth_and_order(trace_on):
    with tracing.span("outer", "exec") as so:
        so.set("k", 1)
        with tracing.span("inner", "exec"):
            with tracing.span("innermost", "plan"):
                pass
    evs = tracing.events()
    by = {e["name"]: e for e in evs}
    assert by["outer"]["depth"] == 0
    assert by["inner"]["depth"] == 1
    assert by["innermost"]["depth"] == 2
    # children close (and record) before parents; parents contain children
    assert evs.index(by["innermost"]) < evs.index(by["inner"]) \
        < evs.index(by["outer"])
    o, i = by["outer"], by["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-6
    assert o["args"] == {"k": 1}


def test_span_error_attribute(trace_on):
    with pytest.raises(ValueError):
        with tracing.span("boom", "exec"):
            raise ValueError("x")
    (e,) = [e for e in tracing.events() if e["name"] == "boom"]
    assert e["args"]["error"] == "ValueError"


def test_instant_and_complete_carry_the_cause(trace_on):
    with tracing.span("job", "job") as j:
        tracing.instant("marker", "exec", {"a": 1})
        tracing.complete("waited", "compile", tracing.now_us() - 5.0, 5.0)
    by = {e["name"]: e for e in tracing.events()}
    assert by["marker"]["dur"] is None and by["waited"]["dur"] == 5.0
    for name in ("marker", "waited"):
        assert by[name]["parent"] == j.id and by[name]["job"] == j.id
        assert by[name]["id"] not in (j.id, None)
    assert not hasattr(tracing, "traced")      # the decorator form is gone


def test_span_records_carry_id_parent_and_job(trace_on):
    with tracing.span("outside", "plan"):
        pass
    with tracing.span("job", "job") as j:
        with tracing.span("stage:execute", "exec") as st:
            with tracing.span("partition:dispatch", "exec"):
                pass
        with tracing.span("collect:box-rows", "exec"):
            pass
    with tracing.span("job", "job") as j2:
        pass
    by = {e["name"]: e for e in tracing.events() if e["id"] != j2.id}
    ids = [e["id"] for e in tracing.events()]
    assert len(set(ids)) == len(ids) and all(isinstance(i, int)
                                             for i in ids)
    assert by["outside"]["parent"] is None and by["outside"]["job"] is None
    assert by["job"]["parent"] is None and by["job"]["job"] == j.id
    assert by["stage:execute"]["parent"] == j.id
    assert by["partition:dispatch"]["parent"] == st.id
    assert by["collect:box-rows"]["parent"] == j.id
    assert {by[n]["job"] for n in ("stage:execute", "partition:dispatch",
                                   "collect:box-rows")} == {j.id}
    assert j2.job == j2.id != j.id             # every job is its own root
    assert by["partition:dispatch"]["depth"] == 2      # depth stays


def test_handoff_adopt_across_threads(trace_on):
    assert tracing.handoff() is None           # nothing open: nothing to hand
    seen = {}

    def worker(h):
        with tracing.adopt(h):
            with tracing.span("worker-top", "io"):
                with tracing.span("worker-inner", "io"):
                    pass
            seen["nested"] = tracing.handoff()   # re-handing what it adopted
        with tracing.span("after-adopt", "io"):
            pass

    with tracing.span("job", "job") as j:
        with tracing.span("submitter", "exec") as sub:
            h = tracing.handoff()
            t = threading.Thread(target=worker, args=(h,))
            t.start()
            t.join()
    by = {e["name"]: e for e in tracing.events()}
    assert by["worker-top"]["parent"] == sub.id
    assert by["worker-top"]["job"] == j.id
    assert by["worker-top"]["depth"] == 0      # top of ITS thread's stack
    assert by["worker-top"]["tid"] != by["submitter"]["tid"]
    assert by["worker-inner"]["parent"] == by["worker-top"]["id"]
    assert by["worker-inner"]["job"] == j.id
    assert seen["nested"] == h
    # adoption ends with the block: pool workers are reused
    assert by["after-adopt"]["parent"] is None
    assert by["after-adopt"]["job"] is None


def test_open_spans_sees_a_span_held_open_on_another_thread(trace_on):
    opened, release = threading.Event(), threading.Event()

    def hold():
        with tracing.span("compile:xla", "compile") as sp:
            sp.set("tag", "held")
            opened.set()
            release.wait(30)

    with tracing.span("job", "job") as j:
        t = threading.Thread(target=hold)
        t.start()
        assert opened.wait(30)
        snap = tracing.open_spans()
        names = [s["name"] for s in snap]
        assert names == ["job", "compile:xla"]          # oldest first
        held = snap[1]
        assert {"name", "ts", "tid", "id", "parent", "job", "args"} \
            <= set(held)
        assert held["args"] == {"tag": "held"} and held["tid"] == t.ident
        assert snap[0]["id"] == j.id
        # still open: not in the ring yet
        assert "compile:xla" not in [e["name"] for e in tracing.events()]
        release.set()
        t.join()
    assert tracing.open_spans() == []
    assert "compile:xla" in [e["name"] for e in tracing.events()]


def test_dropped_counts_ring_evictions(trace_on, monkeypatch):
    from collections import deque

    monkeypatch.setattr(tracing, "_events", deque(maxlen=4))
    assert tracing.dropped() == 0
    for i in range(4):
        tracing.instant(f"e{i}")
    assert tracing.dropped() == 0
    for i in range(3):
        with tracing.span(f"s{i}"):
            pass
    assert tracing.dropped() == 3
    assert [e["name"] for e in tracing.events()] == ["e3", "s0", "s1", "s2"]
    tracing.clear()
    assert tracing.dropped() == 0


def test_disabled_is_noop_singleton_and_records_nothing():
    tracing.enable(False)
    tracing.clear()
    # the disabled fast path returns ONE shared object — no per-call
    # allocation, nothing recorded
    assert tracing.span("a") is tracing.NOOP
    assert tracing.span("b", "exec") is tracing.span("c", "plan")
    with tracing.span("x") as sp:
        sp.set("k", "v")
    tracing.instant("y")
    tracing.complete("z", "exec", 0.0, 1.0)
    assert tracing.events() == []
    # the cross-thread pair is as free: nothing to hand, nothing adopted
    assert tracing.handoff() is None
    assert tracing.adopt(None) is tracing.NOOP
    with tracing.adopt(tracing.handoff()):
        with tracing.span("x"):
            pass
    assert tracing.events() == [] and tracing.open_spans() == []


def test_disabled_zero_allocation_fast_path():
    tracing.enable(False)
    tracing.clear()
    import tracemalloc

    from tuplex_tpu.api.dataset import _harmonize

    def hot():
        tracing.span("hot", "exec")
        with tracing.adopt(tracing.handoff()):
            with tracing.span("dispatch:launch", "exec") as sp:
                sp.set("module", None)
        _harmonize([])            # a call site: `ingest:harmonize`

    for _ in range(64):           # warm any lazy caches
        hot()
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(10000):
        hot()
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(s.size_diff for s in after.compare_to(before, "lineno")
                if s.size_diff > 0 and any(
                    (f.filename or "").replace(os.sep, "/")
                    .endswith("runtime/tracing.py")
                    for f in s.traceback))
    # a couple of transient frames show up as constant noise; what must
    # NOT happen is per-call growth (10k calls would be >=10 KB if span()
    # allocated even one object each)
    assert grown < 512, f"disabled span() allocated {grown} bytes/10k calls"


def test_thread_safety_under_compile_pool(trace_on):
    """Spans opened concurrently on the compile pool's daemon workers:
    per-thread nesting stays consistent and every span records."""
    from tuplex_tpu.exec import compilequeue as CQ

    n_jobs = 8

    def job(i):
        with tracing.span(f"pool-outer-{i}", "compile") as sp:
            sp.set("i", i)
            with tracing.span(f"pool-inner-{i}", "compile"):
                time.sleep(0.03)
        return i

    futs = [CQ.pool().submit(job, i) for i in range(n_jobs)]
    assert sorted(f.result(timeout=30) for f in futs) == list(range(n_jobs))
    evs = tracing.events()
    for i in range(n_jobs):
        (outer,) = [e for e in evs if e["name"] == f"pool-outer-{i}"]
        (inner,) = [e for e in evs if e["name"] == f"pool-inner-{i}"]
        assert outer["tid"] == inner["tid"]          # same worker thread
        assert inner["depth"] == outer["depth"] + 1  # nested ON that thread
        assert inner["ts"] >= outer["ts"]
    # the pool has 4 workers and the jobs overlap: >1 thread recorded
    assert len({e["tid"] for e in evs}) > 1


def test_ring_buffer_bounds_memory(trace_on):
    cap = tracing._events.maxlen
    for i in range(cap + 50):
        tracing.instant(f"e{i}")
    evs = tracing.events()
    assert len(evs) == cap
    assert evs[-1]["name"] == f"e{cap + 49}"   # newest kept, oldest dropped


# ===========================================================================
# chrome export
# ===========================================================================

def test_chrome_trace_event_schema(trace_on, tmp_path):
    with tracing.span("parent", "exec") as sp:
        sp.set("rows", 10)
        with tracing.span("child", "xfer"):
            pass
    tracing.instant("mark", "mem")
    out = tracing.export_chrome_trace(str(tmp_path / "t.json"))
    doc = json.load(open(out))
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    phs = {e["ph"] for e in evs}
    assert "X" in phs and "M" in phs and "i" in phs
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    (p,) = [e for e in evs if e["name"] == "parent"]
    assert p["args"] == {"rows": 10}
    # thread metadata names the lane
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in evs)


def test_dump_and_merge_jsonl(trace_on, tmp_path):
    with tracing.span("hostspan", "exec"):
        pass
    stream = tracing.dump_jsonl(str(tmp_path / "host1.jsonl"))
    loaded = tracing.load_jsonl(stream)
    assert any(e["name"] == "hostspan" for e in loaded)
    merged = tracing.merge_jsonl([stream], str(tmp_path / "merged.json"))
    doc = json.load(open(merged))
    # the local stream AND the per-host stream both land in the merge
    assert sum(1 for e in doc["traceEvents"]
               if e["name"] == "hostspan") == 2


# ===========================================================================
# counter registry
# ===========================================================================

def test_counter_registry_tags_and_delta():
    snap = xferstats.snapshot()
    xferstats.bump("test_ctr", 5, tag="siteA")
    xferstats.bump("test_ctr", 7, tag="siteB")
    xferstats.bump("test_ctr", 0)            # dropped
    xferstats.note_d2h(100, tag="unit")
    xferstats.note_h2d(200, tag="unit")
    d = xferstats.delta(snap)
    assert d["test_ctr"] == 12
    assert d["d2h_bytes"] == 100 and d["d2h_calls"] == 1
    assert d["h2d_bytes"] == 200 and d["h2d_calls"] == 1
    t = xferstats.tags()
    assert t["test_ctr:siteA"] == 5 and t["test_ctr:siteB"] == 7
    assert t["d2h_bytes:unit"] >= 100 and t["h2d_bytes:unit"] >= 200
    assert xferstats.as_dict()["by_tag"]["test_ctr:siteA"] == 5


def test_metrics_expose_transfers_and_counters():
    from tuplex_tpu.api.metrics import Metrics

    m = Metrics()
    m.record_stage({"wall_s": 1.0, "rows_out": 10,
                    "d2h_bytes": 11, "h2d_bytes": 22})
    m.record_stage({"wall_s": 1.0, "rows_out": 10,
                    "d2h_bytes": 100, "h2d_bytes": 200})
    d = m.as_dict()
    assert d["d2h_bytes"] == 111 and d["h2d_bytes"] == 222
    assert isinstance(d["counters"], dict)
    # per-stage breakdown keeps the transfer counters
    assert d["stages"][0]["d2h_bytes"] == 11


def test_metrics_export_trace_requires_spans(tmp_path):
    from tuplex_tpu.api.metrics import Metrics

    tracing.enable(False)
    tracing.clear()
    with pytest.raises(RuntimeError):
        Metrics().export_trace(str(tmp_path / "no.json"))


# ===========================================================================
# compile queue integration
# ===========================================================================

def test_compile_spans_and_cache_attributes(trace_on):
    import numpy as np

    from tuplex_tpu.exec import compilequeue as CQ

    def fn(x):
        return x * 2 + 1

    x = np.arange(64, dtype=np.float32)
    c1 = CQ.compile_traced(fn, (x,), tag="t-span", salt="/trace-test")
    c1(x)
    # second call with the same content address: dedup hit, no compile
    CQ.compile_traced(fn, (x,), tag="t-span", salt="/trace-test")
    names = [e["name"] for e in tracing.events()]
    assert "compile:trace" in names
    assert "compile:cache-hit" in names
    xla = [e for e in tracing.events()
           if e["name"] == "compile:xla" and e["args"].get("tag") == "t-span"]
    aot = [e for e in tracing.events()
           if e["name"] == "compile:aot-load"
           and e["args"].get("cache") == "aot-hit"]
    # a fresh fingerprint compiles (cache=miss attr) unless a previous run
    # of this very test left a disk artifact — then the aot-hit span shows
    assert (xla and xla[0]["args"]["cache"] == "miss") or aot


def test_cpujit_routes_through_compile_queue(monkeypatch):
    """Budget-degraded host-CPU stage compiles are counted/cached via
    compile_traced instead of silently bypassing the queue (ROADMAP)."""
    import numpy as np

    from tuplex_tpu.exec import compilequeue as CQ
    from tuplex_tpu.exec.local import _CpuJit

    # the on-disk AOT store persists across test runs — an artifact from a
    # previous run would serve the executable with zero compiles and void
    # the attribution assertion below
    monkeypatch.setenv("TUPLEX_AOT_CACHE", "0")

    def fn(x):
        return x + 3

    CQ.consume_tag("cpupin-test")            # drain any stale attribution
    j = _CpuJit(fn, tag="cpupin-test", n_ops=2)
    x = np.arange(32, dtype=np.int32)
    out = np.asarray(j(x))
    assert (out == x + 3).all()
    s, n = CQ.consume_tag("cpupin-test")
    assert n >= 1 and s > 0.0                # the compile was ATTRIBUTED
    # same spec again: served from the queue's store, no new compile
    out2 = np.asarray(j(x))
    assert (out2 == x + 3).all()
    s2, n2 = CQ.consume_tag("cpupin-test")
    assert n2 == 0


# ===========================================================================
# recorder: lint rows, span embedding, waterfall + replay
# ===========================================================================

def _synthetic_history(path, with_spans=True):
    job = "deadbeef0001"
    recs = [
        {"event": "job_start", "job": job, "ts": 1000.0,
         "action": "collect", "stages": ["TransformStage"],
         "sample_exception_previews": [],
         "lint": [{"op": "MapOperator", "op_id": 3, "udf": "<lambda>",
                   "kind": "fallback", "reason": "generator in UDF",
                   "loc": "pipe.py:12", "conditional": False}]},
        {"event": "stage_start", "job": job, "ts": 1000.1, "no": 1,
         "kind": "TransformStage", "n_ops": 4},
        {"event": "stage", "job": job, "ts": 1001.5, "no": 1,
         "kind": "TransformStage",
         "metrics": {"wall_s": 1.4, "fast_path_s": 1.0,
                     "slow_path_s": 0.2}, "exception_sample": []},
    ]
    if with_spans:
        recs.append({
            "event": "spans", "job": job, "ts": 1001.6, "n_total": 3,
            "spans": [
                {"name": "job", "cat": "job", "ts": 100.0,
                 "dur": 1500000.0, "tid": 1, "depth": 0},
                {"name": "stage:execute", "cat": "exec", "ts": 200.0,
                 "dur": 1400000.0, "tid": 1, "depth": 1,
                 "args": {"rows_out": 9}},
                {"name": "partition:merge", "cat": "exec", "ts": 300.0,
                 "dur": 200000.0, "tid": 1, "depth": 2}]})
    recs.append({"event": "job_done", "job": job, "ts": 1001.7,
                 "rows": 9, "wall_s": 1.7, "exception_counts": {}})
    with open(path, "w") as fp:
        for r in recs:
            fp.write(json.dumps(r) + "\n")


def test_dashboard_waterfall_and_lint_rows(tmp_path):
    from tuplex_tpu.history.recorder import render_report

    _synthetic_history(str(tmp_path / "tuplex_history.jsonl"))
    out = render_report(str(tmp_path))
    doc = open(out).read()
    # waterfall section with one bar per span, category-colored
    assert "span waterfall" in doc
    assert doc.count("wfbar") >= 3
    assert "cat-exec" in doc and "cat-job" in doc
    assert "partition:merge" in doc
    # lint findings render as per-op rows
    assert "class=lint" in doc
    assert "MapOperator" in doc and "generator in UDF" in doc \
        and "pipe.py:12" in doc


def test_history_to_chrome_replay(tmp_path):
    from tuplex_tpu.history.recorder import history_to_chrome

    # with embedded spans: the replay uses them verbatim
    _synthetic_history(str(tmp_path / "tuplex_history.jsonl"))
    out = history_to_chrome(str(tmp_path), str(tmp_path / "t.json"))
    doc = json.load(open(out))
    names = {e["name"] for e in doc["traceEvents"]}
    assert "stage:execute" in names and "partition:merge" in names
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert xs and min(e["ts"] for e in xs) == 0.0   # normalized per job

    # without spans: coarse bars synthesized from the event wall clocks
    _synthetic_history(str(tmp_path / "tuplex_history.jsonl"),
                       with_spans=False)
    out = history_to_chrome(str(tmp_path), str(tmp_path / "t2.json"))
    doc = json.load(open(out))
    names = {e["name"] for e in doc["traceEvents"]}
    assert "job:collect" in names
    assert "stage1:TransformStage" in names
    (st,) = [e for e in doc["traceEvents"]
             if e["name"] == "stage1:TransformStage"]
    assert abs(st["dur"] - 1.4e6) < 1e3             # 1.4 s in us


def test_history_to_chrome_merges_host_streams(tmp_path):
    """Multihost driver merge: tuplex_trace_host*.jsonl streams dumped
    next to the history file land in the replayed trace, keeping their
    own pid lane (the jax process index from tracing.set_host)."""
    from tuplex_tpu.history.recorder import history_to_chrome

    _synthetic_history(str(tmp_path / "tuplex_history.jsonl"))
    host_ev = {"name": "hostblock:execute", "cat": "exec", "ph": "X",
               "ts": 10.0, "dur": 500.0, "pid": 1, "tid": 7}
    with open(tmp_path / "tuplex_trace_host1.jsonl", "w") as fp:
        fp.write(json.dumps({"name": "process_name", "ph": "M", "pid": 1,
                             "tid": 0,
                             "args": {"name": "tuplex_tpu host1"}}) + "\n")
        fp.write(json.dumps(host_ev) + "\n")
    out = history_to_chrome(str(tmp_path), str(tmp_path / "merged.json"))
    doc = json.load(open(out))
    (got,) = [e for e in doc["traceEvents"]
              if e["name"] == "hostblock:execute"]
    # host lanes offset to 1000+idx so they never collide with job lanes
    assert got["pid"] == 1001 and got["dur"] == 500.0
    job_pids = {e["pid"] for e in doc["traceEvents"]
                if e["name"] != "hostblock:execute"
                and e.get("args") != {"name": "tuplex_tpu host1"}}
    assert got["pid"] not in job_pids
    assert {"name": "tuplex_tpu host1"} in \
        [e.get("args") for e in doc["traceEvents"] if e["ph"] == "M"]


def test_recorder_write_warns_once(tmp_path, caplog):
    import logging

    from tuplex_tpu.history.recorder import JobRecorder

    bad = str(tmp_path / "not-a-dir" / "deeper")     # unwritable logDir
    rec = JobRecorder(bad, enabled=True)
    with caplog.at_level(logging.WARNING):
        rec.job_done(1, 0.1, {})
        rec.job_done(2, 0.2, {})
    warns = [r for r in caplog.records
             if "history write" in r.getMessage()]
    assert len(warns) == 1                            # once, then quiet


def test_job_start_carries_lint_findings(ctx, tmp_path):
    """End-to-end: a plan with a statically non-compilable UDF lands its
    analyzer finding in the recorder's job_start event."""
    ctx.recorder.enabled = True
    ctx.recorder.path = str(tmp_path / "hist.jsonl")

    def gen(x):
        yield x          # generator: fallback finding at plan time

    ds = ctx.parallelize([1, 2, 3]).map(lambda x: x + 1).map(gen)
    try:
        ds.collect()
    except Exception:
        pass             # the job itself may fail; job_start already wrote
    recs = [json.loads(ln) for ln in open(ctx.recorder.path)]
    (start,) = [r for r in recs if r["event"] == "job_start"]
    assert any(f["kind"] == "fallback" and "generator" in f["reason"]
               for f in start["lint"])


# ===========================================================================
# the zillow smoke (tier-1 wiring of scripts/trace_smoke.py)
# ===========================================================================

# ===========================================================================
# spans where the work happens, names on the device, counters at the cause
# ===========================================================================

def _write_csv(path, n):
    with open(path, "w") as fp:
        fp.write("a,b,k\n")
        for i in range(n):
            fp.write(f"{i},{i * 0.5},{'xyz'[i % 3]}\n")
    return os.path.getsize(path)


def _descendants(evs, root_id):
    kids = {}
    for e in evs:
        kids.setdefault(e.get("parent"), []).append(e)
    out, todo = [], [root_id]
    while todo:
        for e in kids.get(todo.pop(), ()):
            out.append(e)
            todo.append(e["id"])
    return out


def test_csv_job_yields_ingest_and_boxing_spans_under_its_job(
        ctx, trace_on, tmp_path):
    p = str(tmp_path / "in.csv")
    size = _write_csv(p, 3000)
    x0 = xferstats.snapshot()
    got = ctx.csv(p).map(lambda x: x["a"] + 1).collect()
    assert got == [i + 1 for i in range(3000)]
    evs = tracing.events()
    (job,) = [e for e in evs if e["name"] == "job"]
    under = _descendants(evs, job["id"])
    names = {e["name"] for e in under}
    assert {"ingest", "ingest:read-csv", "ingest:plan-shapes",
            "ingest:to-partition", "compile:precompile-plan",
            "dispatch:launch", "collect:box-rows"} <= names, names
    # a CSV source cuts its partitions at their final widths: no pad pass
    assert "ingest:harmonize" not in names
    assert all(e["job"] == job["id"] for e in under)
    (read,) = [e for e in under if e["name"] == "ingest:read-csv"]
    # projection pushdown: the pipeline reads one column of the three
    assert read["args"] == {"bytes": size, "rows": 3000, "columns": 1,
                            "file_columns": 3}
    (box,) = [e for e in under if e["name"] == "collect:box-rows"]
    assert box["parent"] == job["id"] and box["args"]["rows"] == 3000
    # the job span covers the boxing: it closes after its last child
    assert box["ts"] + box["dur"] <= job["ts"] + job["dur"] + 1e-6
    d = xferstats.delta(x0)
    assert (d["ingest_bytes"], d["ingest_rows"], d["ingest_files"]) \
        == (size, 3000, 1)
    # before the job: the context's construction is outside this ring
    # (the fixture made it before tracing went on), the sniff is in it
    (sniff,) = [e for e in evs if e["name"] == "ingest:sniff"]
    assert sniff["job"] is None and sniff["ts"] < job["ts"]
    assert sniff["args"]["cached"] in (0, 1)
    launch = [e for e in under if e["name"] == "dispatch:launch"][0]
    assert str(launch["args"]["module"]).startswith("jit_tpx_")
    assert launch["args"]["first_call"] == 1


def test_context_init_span(trace_on):
    import tuplex_tpu

    c = tuplex_tpu.Context()
    (e,) = [e for e in tracing.events() if e["name"] == "context:init"]
    assert e["cat"] == "job" and e["job"] is None
    assert e["args"] == {"backend": "LocalBackend", "devices": 1}
    c.close()


def test_lazy_source_reads_on_the_prefetch_thread_name_the_job(
        trace_on, tmp_path):
    import tuplex_tpu

    c = tuplex_tpu.Context({"tuplex.inputSplitSize": "16KB",
                            "tuplex.sample.maxDetectionRows": "64"})
    p = str(tmp_path / "in.csv")
    _write_csv(p, 20000)                  # a dozen batches of 16 KB
    tracing.clear()
    assert len(c.csv(p).take(5)) == 5
    # take(5) can return before the producer's pull has closed its span:
    # the consumer's exit stops the producer, so wait for its thread
    import threading

    for t in threading.enumerate():
        if t.name == "tuplex-source-prefetch":
            t.join(timeout=60)
    evs = tracing.events()
    (job,) = [e for e in evs if e["name"] == "job"]
    ingest = [e for e in evs if e["name"] == "ingest"]
    assert ingest and all(e["job"] == job["id"] for e in ingest)
    # the first pull is the job thread's own; the producer thread's pulls
    # adopted the consumer's cause: top of their own stack, same job, and
    # a parent that is a span of the job thread
    produced = [e for e in ingest if e["tid"] != job["tid"]]
    assert produced, [(e["tid"], e["depth"]) for e in ingest]
    on_job_thread = {e["id"] for e in evs if e["tid"] == job["tid"]} \
        | {job["id"]}
    for e in produced:
        assert e["depth"] == 0 and e["parent"] in on_job_thread
    waits = [e for e in evs if e["name"] == "source:wait"]
    assert waits and all(e["tid"] == job["tid"] and e["job"] == job["id"]
                         for e in waits)
    reads = [e for e in evs if e["name"] == "ingest:read-csv"]
    assert reads and all(e["parent"] in {i["id"] for i in ingest}
                         for e in reads)
    c.close()


def test_pool_compile_names_the_job_that_submitted_it(
        trace_on, tmp_path):
    import tuplex_tpu
    from tuplex_tpu.exec import compilequeue as CQ

    p = str(tmp_path / "in.csv")
    _write_csv(p, 1500)
    s0 = CQ.snapshot()
    # three stages (decode, map, map): stage 0 is left to its own
    # dispatch, the later ones are the pool's to compile while it runs
    ctx = tuplex_tpu.Context({"tuplex.tpu.maxStageOps": 1})
    ctx.csv(p).map(lambda x: x["a"] * 7 + 3).map(lambda v: v - 11).collect()
    deadline = time.time() + 120          # the speculative compile's spans
    while time.time() < deadline:         # close on the pool, after the job
        evs = tracing.events()
        pool = [e for e in evs if e["name"] == "compile:trace"
                and e["depth"] == 0]
        if pool and not CQ.pending_info()["inflight"]:
            break
        time.sleep(0.05)
    (job,) = [e for e in evs if e["name"] == "job"]
    (pre,) = [e for e in evs if e["name"] == "compile:precompile-plan"]
    assert pre["parent"] == job["id"]
    # one chain (one bucket) went to the pool: stage 1 is not traced yet
    assert pre["args"] == {"stages": 3, "submitted": 1, "skipped": 0}
    assert pool, "no compile:trace span recorded on the pool"
    for e in pool:
        assert e["tid"] != job["tid"]
        assert e["parent"] == pre["id"] and e["job"] == job["id"]
    d = CQ.delta(s0)
    assert d["prewarm_submitted"] == 2 and d["prewarm_skipped"] == 1
    assert 0 <= d["prewarm_used"] <= d["prewarm_submitted"]
    assert d["compile_starts"] >= d["stage_compiles"]
    assert d["compile_starts"] - d["stage_compiles"] \
        - d["compile_failures"] == 0      # nothing left in flight


def _sum_count(a, x):
    return (a[0] + x["a"], a[1] + 1)


def test_aggregate_job_yields_the_four_agg_children(ctx, trace_on,
                                                    tmp_path):
    p = str(tmp_path / "in.csv")
    _write_csv(p, 3000)
    got = (ctx.csv(p).aggregateByKey(
        lambda a, b: (a[0] + b[0], a[1] + b[1]), _sum_count, (0, 0),
        ["k"]).collect())
    assert sorted(got) == [("x", 1498500, 1000), ("y", 1499500, 1000),
                           ("z", 1500500, 1000)]
    evs = tracing.events()
    (agg,) = [e for e in evs if e["name"] == "agg:execute"]
    kids = [e for e in evs if e.get("parent") == agg["id"]]
    names = [e["name"] for e in kids]
    # three keys fit the key table: one launch folds the partition, its
    # groups matched on the device, and the host factorizes nothing
    assert sorted(names) == ["agg:eval-exprs", "agg:host-merge",
                             "agg:host-merge", "agg:segment-fold"], names
    (sf,) = [e for e in kids if e["name"] == "agg:segment-fold"]
    assert sf["args"] == {"slots": 8, "rows": 3000, "groups": 3,
                          "path": "device-table"}
    # the wrapper's self time is what its children leave over
    covered = sum(e["dur"] for e in kids)
    assert covered <= agg["dur"] + 1e-6
    assert covered >= 0.5 * agg["dur"]
    # more keys than the widest table holds: the host factorizes, and the
    # four children are there
    from tuplex_tpu.exec import aggexec as AE

    tracing.clear()
    got = (ctx.csv(p).aggregateByKey(
        lambda a, b: (a[0] + b[0], a[1] + b[1]), _sum_count, (0, 0),
        ["a"]).collect())
    assert len(got) == 3000 > AE._TABLE_MAX_SLOTS
    evs = tracing.events()
    (agg,) = [e for e in evs if e["name"] == "agg:execute"]
    kids = [e for e in evs if e.get("parent") == agg["id"]]
    names = [e["name"] for e in kids]
    for n in ("agg:eval-exprs", "agg:factorize-keys", "agg:segment-fold",
              "agg:host-merge"):
        assert n in names, names
    fk = [e for e in kids if e["name"] == "agg:factorize-keys"][0]
    assert fk["args"] == {"rows": 3000, "groups": 3000}
    assert [e["args"]["path"] for e in kids
            if e["name"] == "agg:segment-fold"][-1] == "host-codes"


def test_stage_module_is_named_and_fingerprint_ignores_the_name(ctx):
    import jax

    from tuplex_tpu.api.dataset import _source_partitions
    from tuplex_tpu.compiler import stagefn as SF
    from tuplex_tpu.exec import compilequeue as CQ
    from tuplex_tpu.plan.physical import plan_stages

    ds = (ctx.parallelize([(i, float(i)) for i in range(500)],
                          columns=["a", "b"])
          .map(lambda x: (x["a"] * 3, x["b"] + 1.5)))
    st = plan_stages(ds._op, ctx.options_store)[0]
    part = _source_partitions(ctx, st, lazy=False)[0]
    avals = SF.partition_avals(part, "q8")
    named = st.build_device_fn(part.schema)
    assert named.__name__ == f"tpx_stage_{st.key()[:8]}"
    plain = st.build_device_fn(part.schema)
    plain.__name__ = plain.__qualname__ = "fn"     # as before this PR
    t_named = jax.jit(named).trace(avals)
    t_plain = jax.jit(plain).trace(avals)
    assert t_named.lower().as_text().lstrip().startswith(
        f"module @jit_tpx_stage_{st.key()[:8]}")
    assert t_plain.lower().as_text().lstrip().startswith("module @jit_fn")
    # nothing stored is orphaned and de-duplication is unchanged
    assert CQ.fingerprint_traced(t_named, salt="/s") \
        == CQ.fingerprint_traced(t_plain, salt="/s")
    gen = st.build_device_fn(part.schema, compaction=False)
    assert gen.__name__.startswith("tpx_stage_")
    # the packed wire's closure carries the same key8 under its own role
    from tuplex_tpu.runtime.packing import PackedStageFn

    traced, _, _ = PackedStageFn(named, donate=False,
                                 tag=st.key()).traced_for(avals)
    assert traced.__name__ == f"tpx_pack_{st.key()[:8]}"
    assert tracing.key8("a", 1) == tracing.key8("a", 1) != tracing.key8("a")


def test_recorder_span_slice_keeps_the_open_job_root(trace_on, tmp_path):
    import tuplex_tpu

    c = tuplex_tpu.Context({"tuplex.webui.enable": True,
                            "tuplex.logDir": str(tmp_path)})
    c.parallelize([1, 2, 3]).map(lambda x: x + 1).collect()
    c.close()
    recs = []
    for name in os.listdir(tmp_path):
        with open(os.path.join(tmp_path, name)) as fp:
            for line in fp:
                try:
                    recs.append(json.loads(line))
                except ValueError:
                    pass
    spans = [r for r in recs if r.get("event") == "spans"]
    assert spans, [r.get("event") for r in recs]
    names = [s["name"] for s in spans[-1]["spans"]]
    assert "job" in names and "collect:box-rows" in names
    (job,) = [s for s in spans[-1]["spans"] if s["name"] == "job"]
    assert job["depth"] == 0 and job["dur"] > 0


def test_trace_smoke_zillow():
    """Acceptance: a zillow run with tuplex.tpu.trace=True produces a
    Chrome trace with nested spans for plan/analyzer/compile (cache
    attr)/dispatch/resolve/merge — asserted inside the script."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("TRACE_SMOKE_ROWS", "400")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_smoke.py")],
        capture_output=True, text=True, timeout=540, env=env, cwd=REPO)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "trace-smoke OK" in r.stdout


# ===========================================================================
# the collect side: boxing a partition at a time, the merge's paths, the
# stage loop's own steps, the memory manager
# ===========================================================================

def _named(evs, name):
    return [e for e in evs if e["name"] == name]


def _ctx_small_parts(**extra):
    import tuplex_tpu

    opts = {"tuplex.partitionSize": "1KB",
            "tuplex.sample.maxDetectionRows": "64"}
    opts.update(extra)
    return tuplex_tpu.Context(opts)


def test_collect_opens_one_box_span_a_partition(trace_on):
    c = _ctx_small_parts()
    got = c.parallelize(list(range(300))).map(
        lambda x: x * 2 if x % 50 else str(x)).collect()
    assert got == [x * 2 if x % 50 else str(x) for x in range(300)]
    evs = tracing.events()
    (outer,) = _named(evs, "collect:box-rows")
    (stage,) = _named(evs, "stage:execute")
    boxes = sorted(_named(evs, "collect:box-partition"),
                   key=lambda e: e["ts"])
    assert len(boxes) >= 3
    # the last stage boxes each partition at its collect, on the job's
    # thread; the action only concatenates what it was handed
    assert all(b["parent"] == stage["id"] and b["tid"] == outer["tid"]
               and b["args"]["in_window"] == 1 for b in boxes)
    assert sum(b["args"]["rows"] for b in boxes) == len(got) \
        == outer["args"]["rows"]
    # the interpreter resolved the str rows, which no int column holds:
    # each partition's native decode is spliced with its fallback rows
    off = 0
    for b in boxes:
        a = b["args"]
        chunk = got[off: off + a["rows"]]
        off += a["rows"]
        assert (a["columns"], a["native"], a["lazy_loads"]) == (1, 1, 0)
        assert a["fallback"] == sum(isinstance(v, str) for v in chunk)
    assert sum(b["args"]["fallback"] for b in boxes) == 6
    # a nested column is boxed by the per-column Python path
    tracing.clear()
    got = c.parallelize(list(range(100))).map(
        lambda x: (x, (x, x + 1))).collect()
    assert got[7] == (7, (7, 8))
    boxes = _named(tracing.events(), "collect:box-partition")
    assert {(b["args"]["native"], b["args"]["columns"]) for b in boxes} \
        == {(0, 2)}
    assert sum(b["args"]["rows"] for b in boxes) == 100


def _id_val_csv(tmp_path, n=3000):
    p = str(tmp_path / "iv.csv")
    with open(p, "w") as fp:
        fp.write("id,val\n")
        for i in range(n):
            fp.write(f"{i % 7},{i % 50}\n")
    return p


def _children(evs, parent):
    return {e["name"]: e for e in evs if e.get("parent") == parent["id"]}


def test_merge_names_its_path_and_the_work_inside(trace_on, tmp_path,
                                                  monkeypatch):
    monkeypatch.setenv("TUPLEX_DEVICE_HANDOFF", "1")
    c = _ctx_small_parts(**{"tuplex.partitionSize": "256KB"})
    p = _id_val_csv(tmp_path)
    # a slow path touched every partition: the data columns come off the
    # device after all, and the resolved rows are spliced among them
    ds = c.csv(p).map(lambda x: {"id": x["id"], "v": 100 // x["val"]}) \
        .resolve(ZeroDivisionError, lambda x: {"id": x["id"], "v": -1})
    got = ds.aggregate(lambda a, b: a + b, lambda a, x: a + x["v"],
                       0).collect()
    assert got == [sum(100 // (i % 50) if i % 50 else -1
                       for i in range(3000))]
    evs = tracing.events()
    merges = _named(evs, "partition:merge")
    assert merges and {m["args"]["path"] for m in merges} == {"resolved"}
    spliced = 0
    for m in merges:
        kids = _children(evs, m)
        fetch, splice = kids["d2h:merge-fetch"], kids["merge:splice"]
        assert fetch["args"]["bytes"] > 0 and fetch["args"]["ready"] in (0, 1)
        assert fetch["ts"] < splice["ts"]
        # the emit order from masks, each leaf one take over a full cover
        # of the partition; every resolved row folded into its slot
        sa = splice["args"]
        assert sa["take"] == 1 and sa["boxed"] == 0
        assert sa["rows_out"] == m["args"]["rows"] == \
            sa["gathered"] + sa["rows"]
        spliced += sa["rows"]
    assert spliced == 3000 // 50
    # nothing resolved: the output stays on the device behind a gathered
    # view, and nothing is fetched
    tracing.clear()
    got = c.csv(p).map(lambda x: {"id": x["id"], "v": x["val"] + 1}) \
        .aggregate(lambda a, b: a + b, lambda a, x: a + x["v"], 0).collect()
    assert got == [sum(i % 50 + 1 for i in range(3000))]
    evs = tracing.events()
    merges = _named(evs, "partition:merge")
    assert merges and {m["args"]["path"] for m in merges} == {"lazy"}
    for m in merges:
        kids = _children(evs, m)
        assert "d2h:merge-fetch" not in kids
        view = kids["handoff:view"]
        assert view["args"]["leaves"] == 2 and view["args"]["bytes"] > 0
    # the job's last stage: a host merge, with its outputs fetched at the
    # head of the collect and nothing spliced
    tracing.clear()
    assert len(c.csv(p).map(lambda x: x["val"] + 1).collect()) == 3000
    evs = tracing.events()
    merges = _named(evs, "partition:merge")
    assert merges and {m["args"]["path"] for m in merges} == {"host"}
    assert not _named(evs, "d2h:merge-fetch") + _named(evs, "merge:splice")


def test_stage_loop_steps_open_their_spans(ctx, trace_on):
    data = [(i, i % 7) for i in range(2000)]
    got = ctx.parallelize(data, columns=["a", "b"]).map(
        lambda x: x["a"] // x["b"]).collect()
    assert len(got) == 2000 - 286
    evs = tracing.events()
    (st,) = _named(evs, "stage:execute")
    kids = sorted((e for e in evs if e.get("parent") == st["id"]),
                  key=lambda e: e["ts"])
    assert all(e["tid"] == st["tid"] for e in kids)
    # each partition: its outputs fetched, its error lattice read into
    # codes, the rows the device classified exactly recorded, the output
    # merged, then its exceptions ordered and committed
    steps = ("partition:collect-fast", "resolve:codes", "resolve:exact-exit",
             "partition:merge", "resolve:record")
    order = [e["name"] for e in kids if e["name"] in steps]
    n = len(_named(kids, "partition:merge"))
    assert n >= 1 and order == list(steps) * n
    # the rows with a code, the records made of them, the records ordered
    # and committed to the exception plane: each partition's 286 in all
    for name in steps[1:]:
        if name != "partition:merge":
            assert sum(e["args"]["rows"] for e in _named(kids, name)) \
                == 286, name
    # what the stage's own seconds leave is the loop itself
    assert sum(e["dur"] for e in kids) <= st["dur"]


def test_spill_and_swap_in_are_spans_where_they_run(trace_on, tmp_path):
    import tuplex_tpu

    c = tuplex_tpu.Context({"tuplex.executorMemory": "64KB",
                            "tuplex.partitionSize": "32KB",
                            "tuplex.scratchDir": str(tmp_path / "scratch")})
    x0 = xferstats.snapshot()
    got = c.parallelize(list(range(20000))).map(lambda x: x * 3).collect()
    assert got == [x * 3 for x in range(20000)]
    evs = tracing.events()
    spills, swaps = _named(evs, "mm:spill"), _named(evs, "mm:swap-in")
    by_id = {e["id"]: e for e in evs}
    # registering an output evicts the oldest, once its rows are boxed:
    # nothing is read back to be boxed
    assert spills and not swaps
    assert {by_id[e["parent"]]["name"] for e in spills} == {"stage:execute"}
    assert {by_id[e["parent"]]["name"]
            for e in _named(evs, "collect:box-partition")} \
        == {"stage:execute"}
    # a sink reads the columns themselves: what was spilled comes back
    tracing.clear()
    c.parallelize(list(range(20000))).map(lambda x: x * 3).tocsv(
        str(tmp_path / "out.csv"))
    evs = tracing.events()
    spills += _named(evs, "mm:spill")
    swaps = _named(evs, "mm:swap-in")
    assert swaps and not _named(evs, "collect:box-partition")
    assert all(e["cat"] == "io" and e["args"]["bytes"] > 0
               for e in spills + swaps)
    assert sum(e["args"]["bytes"] for e in spills) \
        == xferstats.delta(x0)["spill_bytes"] > 0
