"""Zillow Z1 on one chip (`LocalBackend`), held to the benchmark's plain
CPython reference (`bench/configs/zillow-z1/z1.py`, which imports nothing
of the program) on seeded data at a small size, for the two mixes the
benchmark runs: `dirty6` (2% of postal codes broken: the general tier
resolves, the interpreter gets no row) and `postal18` (18%: the sample
widens `postal_code` to str, the general tier gets nothing and the
interpreter retires the blank cells). Also the counters and attributes the
benchmark's readers look for: `compaction_reruns` on the stage record,
`compacted` on `partition:dispatch`, `boxed` on `resolve:interpreter`, and
the packed wire's spans on the job's thread under the job's id."""

import csv
import importlib.util
import os
import random
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(REPO, "bench", "configs", "zillow-z1")
OPTIONS = {"tuplex.tpu.compileDeadlineS": 900}    # the configuration's
ROWS = 4000


def _load(stem):
    """A file of the configuration by path, registered in `sys.modules` so
    that the program's reflection finds the UDFs' source."""
    name = "z1_one_chip_" + stem
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(CONFIG_DIR, stem + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


GEN, Z1 = _load("generate"), _load("z1")
COLUMNS = GEN.COLUMNS["listings"]


@pytest.fixture(autouse=True)
def _as_on_the_chip(monkeypatch):
    """On the chip the compile queue never forks; hold XLA:CPU to that."""
    monkeypatch.setenv("TUPLEX_COMPILE_ISOLATION", "thread")


@pytest.fixture()
def spans():
    from tuplex_tpu.runtime import tracing

    was = tracing.enabled()
    tracing.enable(True)
    tracing.clear()
    yield lambda name: [e for e in tracing.events_since(0)
                        if e["name"] == name and e.get("dur") is not None]
    tracing.enable(was)


def listings(seed, dirty_postal, rows=ROWS):
    rng = random.Random(f"{seed}:listings:0")
    return GEN.gen_chunk("listings", rng, rows, 0,
                         {"dirty_facts": 0.04, "dirty_postal": dirty_postal})


def write(path, rows):
    with open(path, "w", newline="") as fp:
        w = csv.writer(fp)
        w.writerow(COLUMNS)
        w.writerows(rows)
    return {"listings": str(path)}


def run_job(ctx, paths):
    """One `collect()`: (rows, the stage record it left)."""
    n0 = len(ctx.metrics.stages)
    got = Z1.build(ctx, paths).collect()
    (rec,) = ctx.metrics.stages[n0:]
    return got, rec


def assert_equals_reference(got, rows):
    want = Z1.reference_partial(COLUMNS, rows, {})
    assert len(want) > len(rows) // 4
    assert [tuple(r) for r in got] == want        # exact, in input order


def passes_the_earlier_filters(rec):
    """Whether a row reaches the `zipcode` operator in CPython."""
    x = dict(zip(COLUMNS, rec))
    try:
        return Z1.extractBd(x) < 10 and Z1.extractType(x) == "house"
    except Exception:
        return False


def codes_of(span):
    """A `resolve:interpreter` span's `codes` ("TypeError:12,...")."""
    pairs = (kv.rsplit(":", 1)
             for kv in (span["args"].get("codes") or "").split(",") if kv)
    return {k: int(v) for k, v in pairs}


@pytest.mark.parametrize("seed", [33, 4000000033])
def test_dirty6_equals_the_reference_and_the_general_tier_resolves(
        tmp_path, spans, seed):
    import tuplex_tpu

    rows = listings(seed, 0.02)
    ctx = tuplex_tpu.Context(dict(OPTIONS))
    try:
        got, rec = run_job(ctx, write(tmp_path / "l.csv", rows))
    finally:
        ctx.close()
    assert_equals_reference(got, rows)
    assert rec["tier"] == "compiled" and not rec["tier_restarts"]
    assert rec["rows_seen"] == ROWS
    assert rec["resolve_general_rows"] > 0
    assert rec["resolve_interpreter_rows"] == 0
    assert rec["resolve_exact_rows"] > 0       # broken facts: ValueError
    assert rec["compaction_reruns"] == 0
    assert not spans("resolve:interpreter")
    (gen,) = spans("resolve:general")
    assert gen["args"]["rows"] > 0 and gen["args"]["path"]
    (disp,) = spans("partition:dispatch")
    assert disp["args"]["compacted"] == 1 and disp["args"]["rows"] == ROWS


@pytest.mark.parametrize("seed", [33, 4000000033])
def test_postal18_equals_the_reference_and_the_interpreter_retires_rows(
        tmp_path, spans, seed):
    import tuplex_tpu

    rows = listings(seed, 0.18)
    blank = [r for r in rows if r[COLUMNS.index("postal_code")] == ""]
    raising = sum(1 for r in blank if passes_the_earlier_filters(r))
    assert raising > 50
    ctx = tuplex_tpu.Context(dict(OPTIONS))
    try:
        got, rec = run_job(ctx, write(tmp_path / "l.csv", rows))
    finally:
        ctx.close()
    assert_equals_reference(got, rows)
    assert rec["tier"] == "compiled" and not rec["tier_restarts"]
    # the sample read `postal_code` as str: nothing for the general tier,
    # and every blank cell (a null in a str column) falls to CPython
    assert rec["resolve_general_rows"] == 0 and rec["general_path_s"] == 0
    assert not spans("resolve:general")
    assert rec["resolve_interpreter_rows"] == len(blank)
    assert rec["slow_path_s"] > 0
    assert rec["compaction_reruns"] == 0
    (interp,) = spans("resolve:interpreter")
    a = interp["args"]
    assert a["rows"] == len(blank)
    # int(None): every blank row that passes the earlier filters is dropped
    # with TypeError, the others are retired by the filters themselves
    assert codes_of(interp) == {"TypeError": raising}
    assert a["resolved"] == len(blank) - raising
    # none was boxed at ingest: a blank cell rides the columnar path as a
    # null and leaves it by the device's code
    assert a["boxed"] == 0


def test_boxed_counts_the_rows_ingest_boxed(tmp_path, spans):
    """A cell longer than `tuplex.tpu.maxStrBytes` boxes its row at ingest:
    it never rides the columnar path, and `resolve:interpreter` says so."""
    import tuplex_tpu

    rows = listings(34, 0.02, rows=2000)
    addr = COLUMNS.index("address")
    for i in (1500, 1999):
        rows[i][addr] = "9 " + "Long " * 40 + "St"
    ctx = tuplex_tpu.Context(dict(OPTIONS, **{"tuplex.tpu.maxStrBytes": 64}))
    try:
        got, rec = run_job(ctx, write(tmp_path / "l.csv", rows))
    finally:
        ctx.close()
    assert_equals_reference(got, rows)
    (interp,) = spans("resolve:interpreter")
    assert interp["args"]["boxed"] == 2 == interp["args"]["rows"]
    assert rec["resolve_interpreter_rows"] == 2


def _two_columns(path, rows):
    with open(path, "w") as fp:
        fp.write("a,s\n")
        fp.writelines(f"{a},{s}\n" for a, s in rows)
    return str(path)


def _two_column_job(ctx, path):
    n0 = len(ctx.metrics.stages)
    got = (ctx.csv(path)
           .withColumn("b", lambda x: x["a"] * 2)
           .filter(lambda x: x["a"] % 10 < 3)
           .withColumn("c", lambda x: int(x["s"][1:]) + x["b"])
           .mapColumn("s", lambda v: v.upper())).collect()
    return got, ctx.metrics.stages[n0:]     # XLA:CPU's plan may split it


def test_a_bucket_overflow_counts_one_rerun_and_keeps_the_rows(
        tmp_path, spans):
    """The sample (the file's first rows) sees the filter keep no row, the
    partition keeps six in seven: the compaction bucket overflows, the
    partition runs again without compaction, the stage record counts it,
    and the rows are CPython's. (Both columns are read: Z1's own stage
    plans no compaction, PERF.md section 7.)"""
    import tuplex_tpu

    rows = [(5, f"w{i}") for i in range(5000)] + \
           [(1, f"w{i}") for i in range(30000)]
    want = [(a, s.upper(), a * 2, int(s[1:]) + a * 2)
            for a, s in rows if a % 10 < 3]
    path = _two_columns(tmp_path / "o.csv", rows)
    ctx = tuplex_tpu.Context(dict(OPTIONS))
    try:
        got, recs = _two_column_job(ctx, path)
        got2, recs2 = _two_column_job(ctx, path)
    finally:
        ctx.close()
    assert got == want and got2 == want
    assert sum(r["compaction_reruns"] for r in recs) == 1
    # the stage remembers: the next job dispatches without compaction
    assert sum(r["compaction_reruns"] for r in recs2) == 0
    for name in ("partition:dispatch", "partition:collect-fast"):
        assert [s["args"]["compacted"] for s in spans(name)] == [1, 0], name


def test_the_packed_wire_spans_sit_on_the_job_thread_under_its_id(
        tmp_path, spans, monkeypatch):
    """The one-chip path packs its transfers into one buffer a direction
    (off by default on XLA:CPU, forced on here; Z1's packed stage is one
    that XLA:CPU cannot compile, so a two-column stage with a string
    output stands in): the spans a reader sums carry the job's id and lie
    on the thread of its `job` span."""
    import tuplex_tpu

    monkeypatch.setenv("TUPLEX_PACK_TRANSFERS", "1")
    rows = [(i % 7, f"w{i}") for i in range(3000)]
    want = [(a, s.upper(), a * 2, int(s[1:]) + a * 2)
            for a, s in rows if a % 10 < 3]
    ctx = tuplex_tpu.Context(dict(OPTIONS))
    try:
        got, recs = _two_column_job(
            ctx, _two_columns(tmp_path / "p.csv", rows))
    finally:
        ctx.close()
    assert got == want
    (job,) = spans("job")
    by_id = {}
    for name in ("h2d:packed-upload", "d2h:packed-fetch",
                 "d2h:varlen-unpack"):
        found = spans(name)
        assert found, name
        for s in found:
            assert s["job"] == job["id"] and s["tid"] == job["tid"], s
            assert s["args"]["bytes"] > 0
            by_id[s["id"]] = s
    for s in spans("d2h:varlen-unpack"):
        assert by_id[s["parent"]]["name"] == "d2h:packed-fetch"


@pytest.mark.parametrize("n", [1, 4095, 4096, 4097, 9000])
def test_the_varlen_unpack_gathers_block_by_block_what_row_by_row_gives(n):
    """`columns.varlen_to_matrix` (the host side of `d2h:varlen-unpack`)
    fills its byte matrix a block of rows at a time: the blocks' edges,
    a length past the width and a payload that ends early read as one
    row-by-row copy does."""
    import numpy as np

    from tuplex_tpu.runtime.columns import (_VARLEN_BLOCK_ROWS,
                                            varlen_to_matrix)

    assert _VARLEN_BLOCK_ROWS == 4096      # the cases straddle it
    rng = np.random.default_rng(n)
    w = 12
    lens = rng.integers(0, w + 4, n)       # some past the width: clipped
    took = np.clip(lens, 0, w)
    offs = np.concatenate([[0], np.cumsum(took)])[:-1]
    payload = rng.integers(1, 255, int(took.sum()) - 2, dtype=np.uint8)
    want = np.zeros((n, w), np.uint8)
    for i in range(n):
        for j in range(int(took[i])):       # past the end: the last byte
            want[i, j] = payload[min(offs[i] + j, len(payload) - 1)]
    got = varlen_to_matrix(payload, offs, lens, w)
    assert got.dtype == np.uint8 and (got == want).all()
    assert varlen_to_matrix(payload, offs[:0], lens[:0], w).shape == (0, w)
    assert not varlen_to_matrix(payload[:0], offs, lens, w).any()
