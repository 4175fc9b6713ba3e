"""The seven per-layer readers that read the program's cause-carrying
spans and compile-plane counters, each on a hand-made `run`: known spans
and counters give the known value, and a program without them (the parent
commit: no `id`/`parent` on a record, no prewarm counter) gives None."""

import pytest

from harness import spec

CELLS = ["tpch-sf033.q1", "tpch-sf033.q19"]
NEW = ["ingest_share", "dispatch_wait_share", "agg_host_share",
       "unattributed_share", "first_job_unattributed_s",
       "prewarm_hit_share", "compiles_in_flight_at_end"]
S = 1e6                                    # span times are microseconds


def reader(name):
    return spec.Cell(CELLS[0]).reader(name)


def sp(name, ts, dur, tid=1, depth=1, id=None, parent=None, job=None):
    return {"name": name, "cat": "x", "ts": ts * S, "dur": dur * S,
            "tid": tid, "depth": depth, "id": id, "parent": parent,
            "job": job, "args": None}


def window(spans, jobs=(4.0, 6.0), cq=None):
    return {"spans": spans, "cq": cq or {}, "rows": 100,
            "jobs": [{"seconds": s, "fault": None} for s in jobs]}


def run_of(win=None, first=None):
    return {"window": win or window([]),
            "first_job": first or {"seconds": 0.0, "spans": [], "cq": {}}}


JOB_SPANS = [
    # job 1 on thread 1: 4 s, children cover 1.0 + 2.0 + 0.5
    sp("ingest:sniff", 0.0, 0.1, depth=0, id=1),
    sp("job", 0.1, 3.9, depth=0, id=2, job=2),
    sp("ingest", 0.1, 1.0, id=3, parent=2, job=2),
    sp("ingest:read-csv", 0.1, 0.6, depth=2, id=4, parent=3, job=2),
    sp("stage:execute", 1.1, 2.0, id=5, parent=2, job=2),
    sp("dispatch:device-wait", 1.5, 0.25, depth=3, id=6, parent=5, job=2),
    sp("agg:execute", 3.1, 0.5, id=7, parent=2, job=2),
    sp("agg:factorize-keys", 3.1, 0.2, depth=2, id=8, parent=7, job=2),
    sp("agg:host-merge", 3.4, 0.1, depth=2, id=9, parent=7, job=2),
    # job 2: 6 s, children cover 3.0
    sp("job", 4.0, 6.0, depth=0, id=10, job=10),
    sp("ingest", 4.0, 3.0, id=11, parent=10, job=10),
    # a pool compile adopted by job 2: another thread, overlapping
    sp("compile:xla", 4.5, 5.0, tid=2, depth=0, id=12, parent=11, job=10),
    # ... and a span on another thread that claims the job as its parent
    sp("ingest", 4.0, 2.0, tid=3, depth=0, id=13, parent=10, job=10),
]


def test_span_shares_are_named_spans_over_job_seconds():
    r = run_of(window(JOB_SPANS))
    # ingest wrappers 1.0 + 3.0 + 2.0 and the sniff 0.1, not read-csv again
    assert reader("ingest_share").read(r) == pytest.approx(61.0)
    assert reader("dispatch_wait_share").read(r) == pytest.approx(2.5)
    assert reader("agg_host_share").read(r) == pytest.approx(3.0)


def test_unattributed_is_job_seconds_less_the_job_threads_named_time():
    r = run_of(window(JOB_SPANS))
    # named on thread 1: 0.1 + (1.0 + 2.0 + 0.5) + 3.0 = 6.6 of 10 s; the
    # other threads' 5.0 and 2.0 overlap and are not subtracted
    assert reader("unattributed_share").read(r) == pytest.approx(34.0)


def test_first_job_unattributed_counts_init_and_sniff_as_named():
    first = {"seconds": 9.0, "cq": {}, "spans": [
        sp("context:init", 0.0, 1.5, depth=0, id=1),
        sp("ingest:sniff", 1.5, 0.5, depth=0, id=2),
        sp("job", 2.0, 7.0, depth=0, id=3, job=3),
        sp("plan", 2.0, 1.0, id=4, parent=3, job=3),
        sp("ingest", 3.0, 2.0, id=5, parent=3, job=3),
        sp("compile:trace", 3.5, 6.0, tid=9, depth=0, id=6, parent=5,
           job=3)]}
    got = reader("first_job_unattributed_s").read(run_of(first=first))
    assert got == pytest.approx(9.0 - 1.5 - 0.5 - 1.0 - 2.0)


@pytest.mark.parametrize("name", NEW[:5])
def test_span_readers_find_nothing_without_spans(name):
    assert reader(name).read(run_of()) is None
    # the parent commit's records: spans, but no id / parent / job, and
    # none of the new names
    old = [{"name": "job", "ts": 0.0, "dur": 4 * S, "tid": 1, "depth": 0,
            "args": None},
           {"name": "stage:execute", "ts": 0.0, "dur": 2 * S, "tid": 1,
            "depth": 1, "args": None}]
    r = run_of(window(old), {"seconds": 5.0, "spans": old, "cq": {}})
    assert reader(name).read(r) is None


def test_compile_counters():
    first = {"seconds": 1.0, "spans": [], "cq": {
        "prewarm_submitted": 2, "prewarm_used": 1, "compile_starts": 3,
        "stage_compiles": 1, "compile_failures": 1}}
    win = window([], cq={
        "prewarm_submitted": 6, "prewarm_used": 1, "compile_starts": 4,
        "stage_compiles": 2, "compile_failures": 0})
    r = run_of(win, first)
    assert reader("prewarm_hit_share").read(r) == pytest.approx(25.0)
    assert reader("compiles_in_flight_at_end").read(r) == 3


def test_compile_counters_absent_or_nothing_submitted():
    none = {"prewarm_submitted": 0, "prewarm_used": 0, "compile_starts": 0,
            "stage_compiles": 0, "compile_failures": 0}
    r = run_of(window([], cq=dict(none)),
               {"seconds": 1.0, "spans": [], "cq": dict(none)})
    assert reader("prewarm_hit_share").read(r) is None     # 0 submitted
    assert reader("compiles_in_flight_at_end").read(r) == 0
    # the parent commit's counters: neither name is there
    old = {"stage_compiles": 1, "aot_misses": 2}
    r = run_of(window([], cq=dict(old)),
               {"seconds": 1.0, "spans": [], "cq": dict(old)})
    assert reader("prewarm_hit_share").read(r) is None
    assert reader("compiles_in_flight_at_end").read(r) is None


@pytest.mark.parametrize("cell", CELLS)
def test_spec_loads_the_extended_benchmark(cell, benchmark_json):
    c = spec.Cell(cell)
    names = [m["name"] for m in c.per_layer]
    assert names[-7:] == NEW
    for m in c.per_layer[-7:]:
        assert m["workloads"] == CELLS
        assert callable(c.reader(m["name"]).read)
    moved = {m["name"]: m["moves"] for m in benchmark_json["per_layer"]}
    assert moved["first_job_unattributed_s"] == "first_job_s"
    assert {moved[n] for n in NEW if n != "first_job_unattributed_s"} \
        == {"rows_per_s"}
    # the planned cells report none of them until a PR lists them
    assert not set(NEW) & {m["name"] for m in
                           spec.Cell("zillow-z1.dirty6").per_layer}
