"""`agg_device_fold_share` (PR 27) on hand-made span rings: rows of the
by-key folds matched on the device over the rows of all by-key folds."""

import pytest

from harness import spec

S = 1e6                                    # span times are microseconds


def reader():
    return spec.Cell("tpch-sf033.q1").reader("agg_device_fold_share")


def fold(rows, path=None, slots=8, groups=4, ts=0.0):
    args = {"rows": rows, "groups": groups}
    if path is not None:
        args.update(path=path, slots=slots)
    return {"name": "agg:segment-fold", "cat": "exec", "ts": ts * S,
            "dur": 0.01 * S, "tid": 1, "depth": 2, "id": None,
            "parent": None, "job": None, "args": args}


def run_of(spans):
    return {"window": {"spans": spans, "cq": {}, "rows": 100,
                       "jobs": [{"seconds": 4.0, "fault": None}]}}


@pytest.mark.parametrize("spans, want", [
    # every partition matched on the device
    ([fold(1000, "device-table"), fold(600, "device-table")], 100.0),
    # mixed, by rows and not by spans; a miss folded nothing
    ([fold(0, "table-miss"), fold(1000, "host-codes", slots=128),
      fold(3000, "device-table"), fold(0, "table-miss"),
      fold(1000, "host-codes", slots=128)], 60.0),
    # the table never took: all on host-made codes
    ([fold(500, "host-codes", slots=256)], 0.0),
    # no by-key fold of this kind: q19's scalar fold, a program from
    # before the key table, no aggregate, tracing off
    ([fold(7, None, groups=1)], None),
    ([fold(98000, None)], None),
    ([{"name": "job", "ts": 0.0, "dur": 4 * S, "tid": 1, "depth": 0,
       "args": None}], None),
    ([], None),
], ids=["all-device", "mixed-by-rows", "all-host-codes", "scalar-fold",
        "parent-program", "no-aggregate", "no-spans"])
def test_agg_device_fold_share(spans, want):
    got = reader().read(run_of(spans))
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_entry_is_q1s_and_moves_rows_per_s(benchmark_json):
    (m,) = [m for m in benchmark_json["per_layer"]
            if m["name"] == "agg_device_fold_share"]
    assert m == {"name": "agg_device_fold_share", "unit": "%",
                 "better": "higher", "source": "program_span",
                 "layer": "agg/join", "moves": "rows_per_s",
                 "workloads": ["tpch-sf033.q1"]}
