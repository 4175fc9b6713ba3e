"""The four readers of the collect side and the stage loop, each on a
hand-made `run`: known spans give the known value, and a program without
them gives None (the parent commit has no `collect:box-partition` span)."""

import pytest

from harness import spec

BOX = ["zillow-z1-host4.dirty6", "zillow-z1.dirty6", "zillow-z1.postal18",
       "flights-bts.cancelled2"]
SIX = ["tpch-sf033.q1", "tpch-sf033.q19"] + BOX
NEW = {"box_rows_share": BOX, "box_ns_per_cell": BOX, "merge_share": SIX,
       "stage_unattributed_share": SIX}
S = 1e6                                    # span times are microseconds


def reader(name):
    return spec.Cell(NEW[name][0]).reader(name)


def sp(name, ts, dur, tid=1, depth=1, id=None, parent=None, args=None):
    return {"name": name, "cat": "x", "ts": ts * S, "dur": dur * S,
            "tid": tid, "depth": depth, "id": id, "parent": parent,
            "job": 1, "args": args}


def run_of(spans, jobs=(4.0, 6.0)):
    return {"window": {"spans": spans, "cq": {}, "rows": 100,
                       "jobs": [{"seconds": s, "fault": None}
                                for s in jobs]},
            "first_job": {"seconds": 0.0, "spans": [], "cq": {}}}


def box(ts, dur, rows, columns, **kw):
    return sp("collect:box-partition", ts, dur, depth=2, parent=9,
              args=dict(rows=rows, columns=columns, native=1, fallback=0,
                        lazy_loads=0, **kw))


SPANS = [
    sp("job", 0.0, 4.0, depth=0, id=1),
    sp("stage:execute", 0.0, 3.0, id=2, parent=1),
    sp("stage:build", 0.0, 0.1, depth=2, id=3, parent=2),
    sp("partition:collect-fast", 0.1, 1.0, depth=2, id=4, parent=2),
    sp("dispatch:device-wait", 0.1, 0.5, depth=3, id=5, parent=4),
    sp("partition:merge", 1.1, 0.4, depth=2, id=6, parent=2,
       args={"path": "lazy", "rows": 10}),
    sp("resolve:exact-exit", 1.5, 0.2, depth=2, id=7, parent=2),
    # the prefetch thread's pull names the stage as its parent: it
    # overlaps the stage's time and is not subtracted
    sp("ingest", 0.5, 1.0, tid=2, depth=0, id=8, parent=2),
    sp("collect:box-rows", 3.0, 1.0, id=9, parent=1),
    box(3.0, 0.5, rows=1000, columns=10),
    box(3.5, 0.4, rows=1000, columns=10),
    # a second job: its stage has no children at all
    sp("job", 4.0, 6.0, depth=0, id=10),
    sp("stage:execute", 4.0, 1.0, id=11, parent=10),
    sp("partition:merge", 5.0, 0.6, id=12, parent=10,
       args={"path": "resolved", "rows": 10}),
]


def test_box_readers_read_the_partition_spans():
    r = run_of(SPANS)
    # 0.9 s of boxing over 10 s of jobs; 0.9 s over 20,000 cells
    assert reader("box_rows_share").read(r) == pytest.approx(9.0)
    assert reader("box_ns_per_cell").read(r) == pytest.approx(45_000.0)


def test_merge_share_sums_every_merge():
    assert reader("merge_share").read(run_of(SPANS)) == pytest.approx(10.0)


def test_stage_unattributed_subtracts_same_thread_children_only():
    # stage 1: 3.0 - (0.1 + 1.0 + 0.4 + 0.2) = 1.3, the other thread's 1.0
    # and the grandchild wait not subtracted; stage 2: 1.0, none
    got = reader("stage_unattributed_share").read(run_of(SPANS))
    assert got == pytest.approx(23.0)


@pytest.mark.parametrize("name", sorted(NEW))
def test_readers_find_nothing_without_their_spans(name):
    assert reader(name).read(run_of([])) is None
    # the parent commit's records: `collect:box-rows` with no partition
    # spans under it, and no `id` where a record predates them
    old = [{"name": "job", "ts": 0.0, "dur": 4 * S, "tid": 1, "depth": 0,
            "args": None},
           {"name": "collect:box-rows", "ts": 0.0, "dur": 2 * S, "tid": 1,
            "depth": 1, "args": {"rows": 10}},
           {"name": "stage:execute", "ts": 0.0, "dur": 2 * S, "tid": 1,
            "depth": 1, "args": None}]
    assert reader(name).read(run_of(old)) is None


def test_box_ns_per_cell_needs_cells():
    spans = [box(0.0, 0.5, rows=0, columns=3)]
    assert reader("box_ns_per_cell").read(run_of(spans)) is None
    assert reader("box_rows_share").read(run_of(spans)) \
        == pytest.approx(5.0)


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_entries_list_their_cells(name, benchmark_json):
    (m,) = [m for m in benchmark_json["per_layer"] if m["name"] == name]
    assert m["workloads"] == NEW[name]
    assert (m["source"], m["moves"], m["better"]) \
        == ("program_span", "rows_per_s", "lower")
    assert m["layer"] == ("collect" if name.startswith("box")
                          else "stage exec")
    for cell in NEW[name]:
        assert name in [x["name"] for x in spec.Cell(cell).per_layer]
