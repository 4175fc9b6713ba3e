"""`box_in_window_share` on hand-made spans: the rows boxed inside the
stage's dispatch window over all the rows boxed, and None where no
`collect:box-partition` span carries `in_window` (a program that boxes
only after the plan)."""

import pytest

from harness import spec

CELLS = ["zillow-z1-host4.dirty6", "zillow-z1.dirty6", "zillow-z1.postal18",
         "flights-bts.cancelled2"]


def reader():
    return spec.Cell(CELLS[0]).reader("box_in_window_share")


def box(rows, **args):
    return {"name": "collect:box-partition", "cat": "exec", "ts": 0.0,
            "dur": 1000.0, "tid": 1, "depth": 2, "id": None, "parent": None,
            "job": 1, "args": dict(rows=rows, columns=10, native=1,
                                   fallback=0, lazy_loads=0, **args)}


def run_of(spans):
    return {"window": {"spans": spans, "cq": {}, "rows": 100,
                       "jobs": [{"seconds": 1.0, "fault": None}]},
            "first_job": {"seconds": 0.0, "spans": [], "cq": {}}}


@pytest.mark.parametrize("flags, want", [
    ((1, 1, 1), 100.0),                 # every partition in the window
    ((0, 0, 0), 0.0),                   # every partition after the plan
    ((1, 1, 0), 100.0 * 2000 / 2500),   # the rows, not the spans, count
])
def test_reads_the_rows_boxed_in_the_window(flags, want):
    spans = [box(r, in_window=f) for r, f in zip((1000, 1000, 500), flags)]
    assert reader().read(run_of(spans)) == pytest.approx(want)


def test_reads_nothing_without_the_attribute():
    # the parent commit's spans: no `in_window`
    assert reader().read(run_of([box(1000), box(500)])) is None
    assert reader().read(run_of([])) is None
    # nothing boxed: no share to read
    assert reader().read(run_of([box(0, in_window=1)])) is None


def test_the_entry_lists_the_boxing_cells(benchmark_json):
    (m,) = [m for m in benchmark_json["per_layer"]
            if m["name"] == "box_in_window_share"]
    assert m["workloads"] == CELLS
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) \
        == ("%", "higher", "program_span", "collect", "rows_per_s")
    for cell in CELLS:
        assert "box_in_window_share" in \
            [x["name"] for x in spec.Cell(cell).per_layer]
