"""The weblogs deployment through the harness: `weblogs-regex` and its cell
`weblogs-regex.malicious5` are entries added to `BENCHMARK.json` over new
files; the helper pool's tables are a function of the seed; `compare`
passes the reference against itself with the drawn letters drawn again,
and fails the control and a constant letter. The pipeline through the
program is held in `tests/test_weblogs.py`, which tier-1 runs."""

import json
import os
import random
import string
import subprocess
import sys

from harness import datagen, spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "bench", "run.py")
CELL = "weblogs-regex.malicious5"


def test_the_cell_loads_on_one_chip_with_its_metrics():
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and cell.config_name == "weblogs-regex"
    assert cell.pipeline_name == "weblogs"
    assert cell.job_tables() == ("logs", "bad_ips")
    assert {t: v["rows"] for t, v in cell.tables.items()} == {
        "logs": 1000000, "bad_ips": 5000}
    assert cell.params["malformed"] == 0.01
    assert cell.params["home_pages"] == 0.15
    assert cell.params["bad_ip_share"] == 0.05
    assert cell.context_options == {"tuplex.tpu.compileDeadlineS": 900}
    assert cell.limits == {"rows_missing_or_extra": 0, "rows_differ": 0,
                           "letters_not_upper": 0, "letter_share_off": 1}
    names = {m["name"] for m in cell.per_layer}
    assert "regex_fallback_share" in names
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
    a = datagen.generate(inline_pool, cell, 4000000039, str(tmp_path / "a"))
    b = datagen.generate(inline_pool, cell, 4000000039, str(tmp_path / "b"))
    c = datagen.generate(inline_pool, cell, 40, str(tmp_path / "c"))
    assert a.rows == {"logs": 2000, "bad_ips": 200}
    with open(a.paths["logs"], "rb") as fa, \
            open(b.paths["logs"], "rb") as fb, \
            open(c.paths["logs"], "rb") as fc:
        same, other = fa.read(), fc.read()
        assert same == fb.read() and same != other
        assert same.split(b"\r\n", 1)[0] == b"logline"
    datagen.start_reference(inline_pool, cell, a)
    want = datagen.merge_reference(cell, datagen.wait_reference(a))
    assert 60 < len(want) < 140 and len(want[0]) == 7


def _redrawn(pipe, want, letters):
    """The reference's rows as a program would give them: the ten letters
    of each anonymized endpoint drawn by `letters`."""
    out = []
    for row in want:
        hit = pipe.anonymized(row)
        row = list(row)
        if hit:
            row[3] = row[3][:2] + letters() + row[3][12:]
        out.append(tuple(row))
    return out


def test_compare_passes_the_reference_redrawn_and_fails_a_constant_letter(
        inline_pool, tmp_path):
    cell = spec.Cell(CELL)
    cell.scale_rows(20000)
    inp = datagen.generate(inline_pool, cell, 7, str(tmp_path / "w"))
    datagen.start_reference(inline_pool, cell, inp)
    want = datagen.merge_reference(cell, datagen.wait_reference(inp))
    pipe = cell.pipeline()
    rng = random.Random(3)
    fair = _redrawn(pipe, want, lambda: "".join(
        rng.choice(string.ascii_uppercase) for _ in range(10)))
    got = {n: v for n, v, _ in pipe.compare(fair, want, cell.limits)}
    assert got["rows_missing_or_extra"] == got["rows_differ"] == 0
    assert got["letters_not_upper"] == 0 and got["letter_share_off"] < 1
    flat = {n: v for n, v, _ in pipe.compare(
        _redrawn(pipe, want, lambda: "Q" * 10), want, cell.limits)}
    assert flat["letter_share_off"] > 20
    lower = {n: v for n, v, _ in pipe.compare(
        _redrawn(pipe, want, lambda: "q" * 10), want, cell.limits)}
    assert lower["letters_not_upper"] == \
        sum(map(pipe.anonymized, want)) > 0
    # one row lost is one row missing, not a difference in every row after
    short = fair[:40] + fair[41:]
    lost = {n: v for n, v, _ in pipe.compare(short, want, cell.limits)}
    assert lost["rows_missing_or_extra"] == 1 and lost["rows_differ"] == 0


def test_the_control_is_not_correct():
    p = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "4000000040",
         "--seconds", "1", "--trace", "0", "--rehearse", "--rows", "20000",
         "--control"], cwd=ROOT, capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["control"] is True
    c = line["compared"]
    assert c["rows_missing_or_extra"]["value"] == 0
    # the home pages left as they are: every anonymized row differs
    assert 100 < c["rows_differ"]["value"] < 250
