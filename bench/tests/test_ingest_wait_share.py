"""`ingest_wait_share` (PR 32) on hand-made span rings: seconds of the
`ingest`, `ingest:sniff` and `source:wait` spans on a job's thread over the
window's job seconds; what the prefetch thread cuts is not counted."""

import pytest

from harness import spec

S = 1e6                                    # span times are microseconds
CELLS = ["tpch-sf033.q1", "tpch-sf033.q19", "zillow-z1-host4.dirty6"]


def reader(cell=CELLS[0]):
    return spec.Cell(cell).reader("ingest_wait_share")


def sp(name, ts, dur, tid=1, depth=1, id=None, parent=None, job=None):
    return {"name": name, "cat": "io", "ts": ts * S, "dur": dur * S,
            "tid": tid, "depth": depth, "id": id, "parent": parent,
            "job": job, "args": None}


def run_of(spans, jobs=(4.0, 6.0)):
    return {"window": {"spans": spans, "cq": {}, "rows": 100,
                       "jobs": [{"seconds": s, "fault": None}
                                for s in jobs]}}


STREAMED = [
    # job 1 on thread 1 (4 s): the read, the first cut, two waits
    sp("ingest:sniff", 0.0, 0.1, depth=0, id=1),
    sp("job", 0.1, 3.9, depth=0, id=2, job=2),
    sp("ingest", 0.1, 0.6, id=3, parent=2, job=2),
    sp("ingest:read-csv", 0.1, 0.5, depth=2, id=4, parent=3, job=2),
    sp("ingest:plan-shapes", 0.6, 0.05, depth=2, id=5, parent=3, job=2),
    sp("stage:execute", 0.7, 3.0, id=6, parent=2, job=2),
    sp("ingest", 0.7, 0.2, depth=2, id=7, parent=6, job=2),
    sp("ingest:to-partition", 0.7, 0.2, depth=3, id=8, parent=7, job=2),
    sp("source:wait", 1.0, 0.05, depth=3, id=9, parent=6, job=2),
    sp("source:wait", 2.0, 0.05, depth=3, id=10, parent=6, job=2),
    # ... and the producer's cuts for it, beside the chip: not the job's
    sp("ingest", 0.9, 0.8, tid=7, depth=0, id=11, parent=6, job=2),
    sp("ingest:to-partition", 0.9, 0.8, tid=7, id=12, parent=11, job=2),
    # job 2 on thread 1 (6 s): the read alone
    sp("job", 4.0, 6.0, depth=0, id=13, job=13),
    sp("ingest", 4.0, 1.0, id=14, parent=13, job=13),
    sp("ingest", 5.2, 2.0, tid=7, depth=0, id=15, parent=13, job=13),
]

# the parent's program: one `ingest` span a job holds the whole load
LOADED = [
    sp("job", 0.0, 4.0, depth=0, id=1, job=1),
    sp("ingest", 0.0, 2.0, id=2, parent=1, job=1),
    sp("ingest:harmonize", 1.5, 0.5, depth=2, id=3, parent=2, job=1),
    sp("source:wait", 2.5, 0.0, depth=3, id=4, parent=1, job=1),
    sp("job", 4.0, 6.0, depth=0, id=5, job=5),
    sp("ingest", 4.0, 3.0, id=6, parent=5, job=5),
]


@pytest.mark.parametrize("spans, want", [
    # 0.1 + (0.6 + 0.2 + 0.05 + 0.05) + 1.0 of 10 s; thread 7's 2.8 s not
    (STREAMED, 20.0),
    (LOADED, 50.0),
    # jobs on two threads (a service): each thread's own spans count
    ([sp("job", 0.0, 4.0, tid=1, depth=0, id=1, job=1),
      sp("ingest", 0.0, 1.0, tid=1, id=2, parent=1, job=1),
      sp("job", 0.0, 6.0, tid=2, depth=0, id=3, job=3),
      sp("source:wait", 1.0, 0.5, tid=2, depth=2, id=4, parent=3, job=3),
      sp("ingest", 1.0, 3.0, tid=9, depth=0, id=5, parent=3, job=3)], 15.0),
    # a job that read nothing through these spans; tracing off
    ([sp("job", 0.0, 4.0, depth=0, id=1, job=1)], None),
    ([], None),
    # records without `id` (a program from before the cause chain)
    ([{"name": "job", "ts": 0.0, "dur": 4 * S, "tid": 1, "depth": 0,
       "args": None},
      {"name": "ingest", "ts": 0.0, "dur": 2 * S, "tid": 1, "depth": 1,
       "args": None}], None),
], ids=["streamed", "loaded-whole", "two-job-threads", "no-ingest",
        "no-spans", "no-ids"])
def test_ingest_wait_share(spans, want):
    got = reader().read(run_of(spans))
    assert got == (pytest.approx(want) if want is not None else None)


def test_ingest_share_keeps_counting_every_thread():
    r = run_of(STREAMED)
    # the wrappers of both threads and the sniff: 0.1 + 0.6 + 0.2 + 0.8 +
    # 1.0 + 2.0 of 10 s
    assert spec.Cell(CELLS[0]).reader("ingest_share").read(r) \
        == pytest.approx(47.0)


@pytest.mark.parametrize("cell", CELLS)
def test_the_entry_lists_the_three_cells_and_moves_rows_per_s(
        cell, benchmark_json):
    (m,) = [m for m in benchmark_json["per_layer"]
            if m["name"] == "ingest_wait_share"]
    assert m == {"name": "ingest_wait_share", "unit": "%", "better": "lower",
                 "source": "program_span", "layer": "ingest",
                 "moves": "rows_per_s", "workloads": CELLS}
    c = spec.Cell(cell)
    assert [x["name"] for x in c.per_layer].count("ingest_wait_share") == 1
    assert callable(reader(cell).read)
