"""The flights deployment (PR 35) through the harness: `flights-bts` and
its cell `flights-bts.cancelled2` are entries added to `BENCHMARK.json`
over new files; the helper pool's tables are a function of the seed at the
source's 110 columns; a rehearsal through `run.py` gives the reference's
answer and the two new metrics; `run.py --control` is not correct. The
generator, the comparison, both controls one by one, the readers and the
planner are held in `tests/test_flights_bts.py`, which tier-1 runs."""

import json
import os
import subprocess
import sys

from harness import datagen, spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "bench", "run.py")
CELL = "flights-bts.cancelled2"
NEW = ["source_columns_kept_share", "join_row_columns"]
JOINED = ["aggjoin_share", "ingest_share", "ingest_wait_share",
          "dispatch_wait_share", "unattributed_share",
          "first_job_unattributed_s", "compiles_in_flight_at_end",
          "first_job_compiles", "resolve_share", "interpreter_row_share",
          "packed_wire_share", "compaction_reruns"]


def test_the_cell_loads_on_one_chip_with_its_metrics():
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and cell.config_name == "flights-bts"
    assert cell.pipeline_name == "flights"
    assert cell.job_tables() == ("flights", "carriers", "airports")
    assert {t: v["rows"] for t, v in cell.tables.items()} == {
        "flights": 400000, "carriers": 1900, "airports": 9300}
    assert cell.params["cancelled"] == 0.019
    assert cell.params["diverted"] == 0.0025
    assert cell.params["delay_causes_filled"] == 0.19
    assert cell.params["unknown_airport"] == 0.03
    # default options and the configuration's deadline: `LocalBackend`
    assert cell.context_options == {"tuplex.tpu.compileDeadlineS": 900}
    assert cell.limits == {"rows_missing_or_extra": 0, "rows_differ": 0,
                           "float_rel_gap": 1e-12}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW + JOINED) <= names
    assert not {"prewarm_hit_share", "agg_host_share", "shard_imbalance",
                "agg_device_fold_share", "mesh_put_share",
                "sharded_fetch_share"} & names
    assert {m["name"] for m in cell.end_to_end} == {
        "rows_per_s", "first_job_s", "setup_s"}
    for n in names:                        # every reader is a file
        assert callable(cell.reader(n).read)


def test_the_helpers_tables_are_a_function_of_the_seed(inline_pool,
                                                       tmp_path):
    cell = spec.Cell(CELL)
    cell.scale_rows(2000)
    a = datagen.generate(inline_pool, cell, 4000000035, str(tmp_path / "a"))
    b = datagen.generate(inline_pool, cell, 4000000035, str(tmp_path / "b"))
    c = datagen.generate(inline_pool, cell, 36, str(tmp_path / "c"))
    assert a.rows == {"flights": 2000, "carriers": 200, "airports": 200}
    for t in cell.job_tables():
        with open(a.paths[t], "rb") as fa, open(b.paths[t], "rb") as fb, \
                open(c.paths[t], "rb") as fc:
            same, other = fa.read(), fc.read()
            assert same == fb.read() and same != other
            assert same.split(b"\n", 1)[0].count(b",") == \
                len(cell.generator().COLUMNS[t]) - 1
    # the reference over the same chunk files, through the pool
    datagen.start_reference(inline_pool, cell, a)
    want = datagen.merge_reference(cell, datagen.wait_reference(a))
    assert 1800 < len(want) <= 2000 and len(want[0]) == 39


def _run(*args, timeout=900):
    return subprocess.run([sys.executable, RUN, *args], cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


def test_a_rehearsal_gives_the_references_answer_and_the_new_metrics():
    """XLA:CPU's `wide-str-compaction` veto sends the source stage to the
    interpreter tier, which the harness counts as a fault on any device:
    the answers and the metrics are held here, the tier on the chip."""
    p = _run("--workload", CELL, "--seed", "4000000035", "--seconds", "2",
             "--trace", "1", "--rehearse", "--rows", "20000")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True
    assert all("interpreter" in f for f in line["faults"]), line["faults"]
    c = line["compared"]
    assert c["rows_missing_or_extra"] == {"value": 0, "limit": 0}
    assert c["rows_differ"] == {"value": 0, "limit": 0}
    assert c["float_rel_gap"]["value"] <= 1e-12
    m = line["metrics"]
    # XLA:CPU stages leaf by leaf: no packed wire there to read
    assert set(NEW + JOINED) - {"packed_wire_share"} <= set(m), sorted(m)
    assert 27 < m["source_columns_kept_share"]["value"] < 28   # 30 of 110,
    assert m["join_row_columns"]["value"] == 36.0     # by bytes; 33, 36, 39
    assert m["aggjoin_share"]["value"] > 0


def test_the_control_is_not_correct():
    p = _run("--workload", CELL, "--seed", "4000000036", "--seconds", "1",
             "--trace", "0", "--rehearse", "--rows", "20000", "--control")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["control"] is True
    c = line["compared"]
    assert 800 < c["rows_missing_or_extra"]["value"] < 1400
    assert c["rows_differ"]["value"] == 0
    assert 1e-9 < c["float_rel_gap"]["value"] < 1e-6
