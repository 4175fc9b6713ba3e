"""The one-chip Zillow deployment (PR 33): `zillow-z1` and its two cells,
`zillow-z1.dirty6` and `zillow-z1.postal18`, are entries added to
`BENCHMARK.json` over files that were here (and one new traffic file);
`bench/planned.json` is neither moved nor read first; the three new readers
on hand-made runs; and the new cell end to end through `Context` at its
test size, with its control."""

import json
import os
import subprocess
import sys

import pytest

from harness import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "bench", "run.py")
CELLS = ["zillow-z1.dirty6", "zillow-z1.postal18"]
NEW = ["packed_wire_share", "interpreter_us_per_row", "compaction_reruns"]
# where each new reader finds something to read: no row of dirty6 goes to
# the interpreter, and a listed metric has to be in the cell's traced line
LISTED = {"packed_wire_share": CELLS, "interpreter_us_per_row": CELLS[1:],
          "compaction_reruns": CELLS}
# accepted lists that take both cells at their end
JOINED = ["ingest_share", "dispatch_wait_share", "unattributed_share",
          "first_job_unattributed_s", "compiles_in_flight_at_end",
          "ingest_wait_share", "resolve_share", "interpreter_row_share",
          "first_job_compiles"]
S = 1e6                                    # span times are microseconds


@pytest.fixture(scope="module")
def bm():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fp:
        return json.load(fp)


@pytest.mark.parametrize("name, postal", zip(CELLS, [0.02, 0.18]))
def test_a_cell_loads_on_one_chip_with_its_metrics(name, postal):
    cell = spec.Cell(name)
    assert cell.chips == 1 and cell.config_name == "zillow-z1"
    assert cell.pipeline_name == "z1"
    assert cell.tables == {"listings": {"rows": 1000000,
                                        "chunk_rows": 50000}}
    assert cell.params["dirty_facts"] == 0.04
    assert cell.params["dirty_postal"] == postal
    # default options and the configuration's deadline: `LocalBackend`
    assert cell.context_options == {"tuplex.tpu.compileDeadlineS": 900}
    assert cell.limits == {"rows_missing_or_extra": 0, "rows_differ": 0}
    names = {m["name"] for m in cell.per_layer}
    assert {n for n in NEW if name in LISTED[n]} | set(JOINED) <= names
    assert ("interpreter_us_per_row" in names) == (name == CELLS[1])
    assert not {"prewarm_hit_share", "agg_host_share", "aggjoin_share",
                "shard_imbalance", "mesh_put_share",
                "sharded_fetch_share"} & names
    assert {m["name"] for m in cell.end_to_end} == {
        "rows_per_s", "first_job_s", "setup_s"}
    for n in names:                        # every reader is a file
        assert callable(cell.reader(n).read)


def test_the_entries_are_added_and_win_over_the_planned_ones(bm):
    (cfg,) = [c for c in bm["configs"] if c["name"] == "zillow-z1"]
    with open(os.path.join(ROOT, cfg["file"])) as fp:
        stated = json.load(fp)
    assert cfg["source"] == stated["source"]               # word for word
    assert cfg["reduced"] == stated["reduced"] == ["tables"]
    mine = [w for w in bm["workloads"] if w["name"] in CELLS]
    assert [w["name"] for w in mine] == CELLS
    assert [w["name"] for w in bm["workloads"]][-2:] == CELLS
    assert all(w["chips"] == 1 and w["config"] == "zillow-z1"
               and len(w["why"]) <= 200 for w in mine)
    assert sum(1 for w in bm["workloads"] if w["chips"] == 4) == 1
    # `bench/planned.json` is as it was; BENCHMARK.json's entry comes first
    # and is the one `spec.Cell` takes
    with open(os.path.join(ROOT, "bench", "planned.json")) as fp:
        planned = json.load(fp)
    assert "zillow-z1.dirty6" in [w["name"] for w in planned["workloads"]]
    first = next(w for w in spec.entries(ROOT)["workloads"]
                 if w["name"] == "zillow-z1.dirty6")
    assert first == mine[0]


def test_the_lists_take_both_cells_at_their_end(bm):
    by_name = {m["name"]: m for m in bm["per_layer"]}
    for n in JOINED:
        assert by_name[n]["workloads"][-2:] == CELLS, n
    for n in NEW:
        assert by_name[n]["workloads"] == LISTED[n], n
    assert [m["name"] for m in bm["per_layer"]][-3:] == NEW
    assert {n: (by_name[n]["layer"], by_name[n]["unit"], by_name[n]["moves"],
                by_name[n]["better"]) for n in NEW} == {
        "packed_wire_share": ("transfer", "%", "rows_per_s", "lower"),
        "interpreter_us_per_row": ("resolve", "us/row", "rows_per_s",
                                   "lower"),
        "compaction_reruns": ("stage exec", "count", "rows_per_s", "lower")}
    for n in ("prewarm_hit_share", "aggjoin_share", "shard_imbalance"):
        assert not set(CELLS) & set(by_name[n]["workloads"]), n
    names = [m["name"] for m in bm["per_layer"]]
    assert len(names) == len(set(names))


# ---- the three new readers ----

def span(name, dur_s, args=None):
    return {"name": name, "cat": "xfer", "ts": 0.0, "dur": dur_s * S,
            "tid": 1, "depth": 3, "id": None, "parent": None, "job": None,
            "args": args}


def run_of(spans=(), stages=(), job_s=(2.0, 2.0)):
    return {"window": {"spans": list(spans), "stages": list(stages),
                       "cq": {}, "rows": 100,
                       "jobs": [{"seconds": s, "fault": None}
                                for s in job_s]}}


def reader(name):
    return spec.Cell(CELLS[1]).reader(name)


@pytest.mark.parametrize("spans, want", [
    ([span("h2d:packed-upload", 0.3), span("d2h:packed-fetch", 0.1)], 10.0),
    # the varlen unpack lies inside the fetch and is not counted again
    ([span("h2d:packed-upload", 0.1), span("d2h:packed-fetch", 0.3),
      span("d2h:varlen-unpack", 0.2), span("h2d:leaf-stage", 1.0),
      span("d2h:leaf-fetch", 1.0)], 10.0),
    ([span("d2h:packed-fetch", 0.2)], 5.0),
    # the mesh backend, the parent's program with tracing off
    ([span("h2d:leaf-stage", 1.0), span("d2h:leaf-fetch", 1.0)], None),
    ([], None),
], ids=["both", "fetch-holds-unpack", "fetch-alone", "leaf-by-leaf",
        "no-spans"])
def test_packed_wire_share(spans, want):
    got = reader("packed_wire_share").read(run_of(spans))
    assert got == (pytest.approx(want) if want is not None else None)


def test_packed_wire_share_without_job_seconds_is_none():
    run = run_of([span("h2d:packed-upload", 0.3)], job_s=())
    assert reader("packed_wire_share").read(run) is None


@pytest.mark.parametrize("stages, want", [
    ([{"slow_path_s": 0.5, "resolve_interpreter_rows": 25000},
      {"slow_path_s": 0.7, "resolve_interpreter_rows": 35000}], 20.0),
    # dirty6: the tier's clock ran (a few microseconds a partition) and
    # retired no row
    ([{"slow_path_s": 4e-5, "resolve_interpreter_rows": 0}], None),
    # a program whose records lack the counter
    ([{"slow_path_s": 0.5}], None),
    ([{"resolve_interpreter_rows": 10}], None),
    ([], None),
], ids=["rows", "no-row", "no-counter", "no-seconds", "no-stage"])
def test_interpreter_us_per_row(stages, want):
    got = reader("interpreter_us_per_row").read(run_of(stages=stages))
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("stages, want", [
    ([{"compaction_reruns": 0}, {"compaction_reruns": 0}], 0),
    ([{"compaction_reruns": 1}, {"compaction_reruns": 0},
      {"compaction_reruns": 2}], 3),
    # the parent's program: no such key on a stage record
    ([{"fast_path_s": 1.0}], None),
    ([], None),
], ids=["none", "three", "no-counter", "no-stage"])
def test_compaction_reruns(stages, want):
    got = reader("compaction_reruns").read(run_of(stages=stages))
    assert got == want and (want is None or isinstance(got, int))


# ---- the new cell end to end ----

def _run(*args, timeout=600):
    return subprocess.run([sys.executable, RUN, *args], cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


def test_a_rehearsal_of_postal18_is_correct_and_the_interpreter_works():
    p = _run("--workload", CELLS[1], "--seed", "4000000033", "--seconds",
             "2", "--trace", "1", "--rehearse", "--rows", "20000")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, \
        (line["faults"], line["compared"])
    assert line["device"]["count"] >= 1 and line["rehearsal"] is True
    assert line["compared"]["rows_differ"] == {"value": 0, "limit": 0}
    m = line["metrics"]
    # XLA:CPU stages leaf by leaf: no packed wire there to read
    assert set(NEW + JOINED) - {"packed_wire_share"} <= set(m), sorted(m)
    assert 4.5 < m["interpreter_row_share"]["value"] < 7.5
    assert 0 < m["interpreter_us_per_row"]["value"] < 1000
    assert 0 < m["resolve_share"]["value"] < 100
    assert m["compaction_reruns"]["value"] == 0
    assert m["window_compiles"]["value"] == 0


def test_a_rehearsal_of_dirty6_reads_no_interpreter_row():
    p = _run("--workload", CELLS[0], "--seed", "4000000034", "--seconds",
             "2", "--trace", "1", "--rehearse", "--rows", "20000")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, \
        (line["faults"], line["compared"])
    m = line["metrics"]
    assert m["interpreter_row_share"]["value"] == 0
    assert "interpreter_us_per_row" not in m
    assert 0 < m["resolve_share"]["value"] < 100
    assert m["compaction_reruns"]["value"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(name):
    p = _run("--workload", name, "--seed", "4000000035", "--seconds", "1",
             "--trace", "0", "--rehearse", "--rows", "20000", "--control")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["control"] is True
    assert line["compared"]["rows_differ"]["value"] > 0
    assert line["compared"]["rows_missing_or_extra"]["value"] == 0
