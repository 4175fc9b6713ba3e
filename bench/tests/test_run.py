"""The command end to end on the CPU: a rehearsal's last line, the refusal
without a TPU, and `correct` coming out false when the timed path is broken
underneath."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "bench", "run.py")
DEVICE_METRICS = {"device_idle_share", "stage_roofline_share",
                  "peak_hbm_bytes"}


def _run(*args, timeout=600):
    return subprocess.run([sys.executable, RUN, *args], cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("cell,layer_metric", [
    ("tpch-sf033.q1", "aggjoin_share"), ("zillow-z1.dirty6", "resolve_share")])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_ends_with_a_well_formed_line_that_names_the_cpu(
        trace, cell, layer_metric, benchmark_json):
    p = _run("--workload", cell, "--seed", "4000000011",
             "--seconds", "2", "--trace", str(trace), "--rehearse",
             "--rows", "20000")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert line["correct"] is True and line["failed"] == 0, \
        (line["faults"], line["compared"])
    assert line["attempted"] >= 2
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    assert "memory_peak_bytes" not in line["device"]
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert not DEVICE_METRICS & set(line["metrics"])
    assert list(line)[-1] == "compared"
    assert "reference" in p.stderr.split("window")[-1]   # after the window
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    for c in line["compared"]:
        assert f"compared {c} = " in p.stderr
    names = {m["name"] for m in
             benchmark_json["per_layer" if trace else "end_to_end"]}
    assert set(line["metrics"]) <= names
    if trace:
        assert {"plan_ms", "first_job_load_s", "fast_path_share",
                "window_compile_starts", "h2d_bytes_per_row",
                layer_metric} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"rows_per_s", "first_job_s",
                                        "setup_s"}
        for m in line["metrics"].values():
            assert m["value"] > 0 and m["unit"]


def test_without_a_tpu_it_refuses_and_prints_no_result():
    p = _run("--workload", "zillow-z1.dirty6", "--seed", "5", "--seconds",
             "1", "--trace", "0")
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_an_unknown_workload_is_refused():
    p = _run("--workload", "no-such-cell", "--seed", "5", "--seconds", "1",
             "--trace", "0", "--rehearse")
    assert p.returncode == 2 and p.stdout.strip() == ""


BROKEN = os.path.join(ROOT, "bench", "tests", "broken_run.py")


def _broken(fault: str, seed: int, cell: str = "zillow-z1.dirty6",
            rows: int = 20000) -> dict:
    p = subprocess.run(
        [sys.executable, BROKEN, fault, "--workload", cell,
         "--seed", str(seed), "--seconds", "1", "--trace", "0", "--rehearse",
         "--rows", str(rows)], cwd=ROOT, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,rows,fault", [
    ("zillow-z1.dirty6", 20000, "drop_half"),
    ("zillow-z1.dirty6", 20000, "alter_one"),
    ("zillow-z1.dirty6", 20000, "no_answer"),
    ("tpch-sf033.q1", 20000, "drop_half"),
    ("tpch-sf033.q1", 20000, "alter_one"),
    ("tpch-sf033.q19", 200000, "drop_half"),
    ("tpch-sf033.q19", 200000, "alter_one"),
])
def test_a_broken_timed_path_comes_out_not_correct(cell, rows, fault):
    """A whole run with the job's answer altered where it is produced:
    half of the answer left out, one value changed, no answer at all."""
    line = _broken(fault, 41, cell, rows)
    assert line["correct"] is False
    assert any(not c["value"] <= c["limit"]
               for c in line["compared"].values())


def test_a_job_that_left_the_device_is_a_failed_job():
    """A stage record that names another tier than `compiled` fails the
    job, and the run is not correct."""
    line = _broken("demoted", 42)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] >= 2
    assert "rows_per_s" not in line["metrics"]


def test_benchmark_json_names_only_what_exists(benchmark_json):
    """Holds for the planned cells' entries (`bench/planned.json`) too."""
    bench = os.path.join(ROOT, "bench")
    for c in benchmark_json["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as fp:
            cfg = json.load(fp)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    names = {c["name"] for c in benchmark_json["configs"]}
    cells = {w["name"] for w in benchmark_json["workloads"]}
    for w in benchmark_json["workloads"]:
        assert w["config"] in names
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert os.path.isfile(os.path.join(bench, "workloads",
                                           w["name"] + ".json"))
    e2e = {m["name"] for m in benchmark_json["end_to_end"]}
    for m in benchmark_json["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert os.path.isfile(os.path.join(bench, "layer_metrics",
                                           m["name"] + ".py"))
