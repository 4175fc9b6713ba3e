"""The four-chip Zillow deployment (PR 29): `zillow-z1-host4` and its cell
`zillow-z1-host4.dirty6` are made of new files and new entries only; the
configuration's copies of the generator and of the pipeline cannot drift
from the planned configuration's; the three new readers on hand-made runs;
and the cell end to end on four virtual devices, with its control."""

import ast
import json
import os
import subprocess
import sys

import pytest

from harness import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "bench", "run.py")
CELL = "zillow-z1-host4.dirty6"
LISTED = ["resolve_share", "interpreter_row_share", "shard_imbalance",
          "mesh_put_share", "first_job_compiles", "sharded_fetch_share"]
# PR 26's span metrics that find something to read on the mesh path: the
# cell is appended to the list each of them had (a name may not repeat)
JOINED = ["ingest_share", "dispatch_wait_share", "unattributed_share",
          "first_job_unattributed_s", "compiles_in_flight_at_end"]
S = 1e6                                    # span times are microseconds


def test_the_cell_loads_with_four_chips_and_its_metrics_once_each():
    cell = spec.Cell(CELL)
    assert cell.chips == 4 and cell.config_name == "zillow-z1-host4"
    assert cell.pipeline_name == "z1"
    assert cell.tables == {"listings": {"rows": 2000000,
                                        "chunk_rows": 50000}}
    assert cell.params["dirty_facts"] == 0.04
    assert cell.params["dirty_postal"] == 0.02
    assert cell.context_options == {
        "tuplex.tpu.compileDeadlineS": 900,
        "tuplex.backend": "multihost", "tuplex.tpu.meshShape": "4"}
    assert cell.limits == {"rows_missing_or_extra": 0, "rows_differ": 0}
    names = [m["name"] for m in cell.per_layer]
    for n in LISTED + JOINED:
        assert names.count(n) == 1, (n, names)
    # the mesh backend submits no prewarm and the job ends in no fold
    assert not {"prewarm_hit_share", "agg_host_share"} & set(names)
    assert len(names) == len(set(names))
    assert {m["name"] for m in cell.end_to_end} == {
        "rows_per_s", "first_job_s", "setup_s"}
    for n in names:                        # every reader is a file
        assert callable(cell.reader(n).read)


def test_the_entries_list_the_cell_alone(benchmark_json):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fp:
        bm = json.load(fp)
    with_cell = [m for m in bm["per_layer"]
                 if CELL in m.get("workloads", ())]
    mine = [m for m in with_cell if m["workloads"] == [CELL]]
    assert [m["name"] for m in mine] == LISTED
    # an accepted list takes the cell at its end and nothing else changes
    assert [m["name"] for m in with_cell if m not in mine] == JOINED
    assert all(m["workloads"] == ["tpch-sf033.q1", "tpch-sf033.q19", CELL]
               for m in with_cell if m not in mine)
    names = [m["name"] for m in bm["per_layer"]]
    assert len(names) == len(set(names))
    assert {m["name"]: (m["layer"], m["moves"], m["better"]) for m in mine} \
        == {"resolve_share": ("resolve", "rows_per_s", "lower"),
            "interpreter_row_share": ("resolve", "rows_per_s", "lower"),
            "shard_imbalance": ("mesh", "rows_per_s", "lower"),
            "mesh_put_share": ("transfer", "rows_per_s", "lower"),
            "first_job_compiles": ("compile", "first_job_s", "lower"),
            "sharded_fetch_share": ("transfer", "rows_per_s", "lower")}
    (w,) = [w for w in bm["workloads"] if w["name"] == CELL]
    assert w["chips"] == 4 and w["config"] == "zillow-z1-host4"
    four = [w for w in bm["workloads"] if w["chips"] == 4]
    assert len(four) == 1 and len(bm["workloads"]) == 3
    # the planned entries are neither moved nor doubled
    assert [w["name"] for w in benchmark_json["workloads"]].count(CELL) == 1


def test_the_configuration_states_its_deployment():
    cfg = spec.Cell(CELL).config
    planned = spec.Cell("zillow-z1.dirty6.mesh4").config
    assert cfg["guarantees"] == planned["guarantees"]      # word for word
    assert cfg["assumed"] == planned["assumed"]
    assert cfg["architecture"] is None
    assert cfg["reduced"] == ["tables"]
    assert cfg["upstream_input_bytes"] == 10_000_000_000
    assert "6.1" in cfg["source"] and "Z1" in cfg["source"]
    assert cfg["source"] != planned["source"]


def _code(path: str) -> str:
    """A module's code without its docstring, as the parser sees it."""
    with open(path) as fp:
        tree = ast.parse(fp.read())
    assert isinstance(tree.body[0], ast.Expr)              # the docstring
    tree.body = tree.body[1:]
    return ast.dump(tree)


@pytest.mark.parametrize("stem", ["generate", "z1"])
def test_the_copies_equal_the_planned_files_but_for_the_docstring(stem):
    configs = os.path.join(ROOT, "bench", "configs")
    assert _code(os.path.join(configs, "zillow-z1-host4", stem + ".py")) \
        == _code(os.path.join(configs, "zillow-z1", stem + ".py"))


def test_the_reference_imports_nothing_of_the_program():
    for stem in ("generate", "z1"):
        with open(os.path.join(ROOT, "bench", "configs", "zillow-z1-host4",
                               stem + ".py")) as fp:
            tree = ast.parse(fp.read())
        mods = {n.module if isinstance(n, ast.ImportFrom) else a.name
                for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom))
                for a in n.names}
        assert mods <= {"__future__"}, mods


# ---- the three new readers ----

def span(name, dur_s, ts=0.0, args=None):
    return {"name": name, "cat": "xfer", "ts": ts * S, "dur": dur_s * S,
            "tid": 1, "depth": 3, "id": None, "parent": None, "job": None,
            "args": args}


def window_of(spans, job_s=(2.0, 2.0)):
    return {"window": {"spans": spans, "cq": {}, "rows": 100,
                       "jobs": [{"seconds": s, "fault": None}
                                for s in job_s]}}


@pytest.mark.parametrize("spans, want", [
    ([span("h2d:mesh-put", 0.3), span("h2d:mesh-put", 0.1)], 10.0),
    # the pad's copy counts beside the put; other spans do not
    ([span("h2d:mesh-put", 0.3), span("mesh:pad-batch", 0.1),
      span("h2d:leaf-stage", 1.0), span("d2h:leaf-fetch", 1.0)], 10.0),
    ([span("mesh:pad-batch", 0.2)], 5.0),
    # a one-chip run, the parent's program, tracing off
    ([span("h2d:leaf-stage", 1.0)], None),
    ([], None),
], ids=["puts", "put-and-pad", "pad-alone", "no-mesh-span", "no-spans"])
def test_mesh_put_share(spans, want):
    got = spec.Cell(CELL).reader("mesh_put_share").read(window_of(spans))
    assert got == (pytest.approx(want) if want is not None else None)


def test_mesh_put_share_without_job_seconds_is_none():
    run = window_of([span("h2d:mesh-put", 0.3)], job_s=())
    assert spec.Cell(CELL).reader("mesh_put_share").read(run) is None


FOUR = {"bytes": 4096, "shards": 24, "devices": 4}


@pytest.mark.parametrize("spans, want", [
    ([span("d2h:leaf-fetch", 0.3, args=FOUR),
      span("d2h:leaf-fetch", 0.1, args=FOUR)], 10.0),
    # the general tier's fetch from the host-CPU executable is not sharded
    ([span("d2h:leaf-fetch", 0.2, args=FOUR),
      span("d2h:leaf-fetch", 1.0, args={"bytes": 64}),
      span("h2d:mesh-put", 1.0)], 5.0),
    # one chip, or the parent's program, which does not count the shards
    ([span("d2h:leaf-fetch", 1.0, args={"bytes": 64}),
      span("d2h:leaf-fetch", 1.0)], None),
    ([], None),
], ids=["sharded", "sharded-beside-plain", "no-shards", "no-spans"])
def test_sharded_fetch_share(spans, want):
    got = spec.Cell(CELL).reader("sharded_fetch_share").read(
        window_of(spans))
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("first_job, want", [
    ({"cq": {"stage_compiles": 0, "aot_hits": 2}}, 0),
    ({"cq": {"stage_compiles": 2, "aot_misses": 2}}, 2),
    ({"cq": {}}, None),
    ({}, None),
], ids=["warm", "cold", "no-counter", "no-record"])
def test_first_job_compiles(first_job, want):
    got = spec.Cell(CELL).reader("first_job_compiles").read(
        {"first_job": first_job})
    assert got == want


# ---- the cell end to end on four virtual devices ----

def _run(*args, timeout=600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    return subprocess.run([sys.executable, RUN, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_a_rehearsal_on_four_devices_is_correct_and_reads_every_metric():
    p = _run("--workload", CELL, "--seed", "4000000029", "--seconds", "2",
             "--trace", "1", "--rehearse", "--rows", "20000")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, \
        (line["faults"], line["compared"])
    assert line["device"]["count"] == 4 and line["rehearsal"] is True
    assert line["compared"]["rows_differ"] == {"value": 0, "limit": 0}
    m = line["metrics"]
    assert set(LISTED + JOINED) <= set(m), sorted(m)
    assert m["shard_imbalance"]["value"] == 100.0
    assert 0 < m["mesh_put_share"]["value"] < 100
    assert 0 < m["sharded_fetch_share"]["value"] < 100
    assert 0 < m["ingest_share"]["value"] < 100
    assert 0 < m["resolve_share"]["value"] < 100
    assert m["first_job_compiles"]["value"] >= 0
    assert m["window_compiles"]["value"] == 0


def test_the_control_is_not_correct():
    p = _run("--workload", CELL, "--seed", "4000000030", "--seconds", "1",
             "--trace", "0", "--rehearse", "--rows", "20000", "--control")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["control"] is True
    assert line["compared"]["rows_differ"]["value"] > 0
    assert line["compared"]["rows_missing_or_extra"]["value"] == 0
