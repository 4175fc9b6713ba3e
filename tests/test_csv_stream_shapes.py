"""A CSV source plans its partitions' shapes from the Arrow table and cuts
them at those shapes as the stage pulls them (PR 32): counts, bytes and
spans — no timings. The parent's route, which the streamed partitions must
equal byte for byte, is `_table_to_partition` at each slice's own width and
then `C.harmonize_partitions` over the lot."""

import glob
import threading
import time

import numpy as np
import pytest

from tuplex_tpu.core import typesys as T
from tuplex_tpu.io import csvsource as CS
from tuplex_tpu.plan.physical import plan_stages
from tuplex_tpu.runtime import columns as C
from tuplex_tpu.runtime import tracing


@pytest.fixture()
def trace_on():
    tracing.clear()
    tracing.enable(True)
    yield
    tracing.enable(False)
    tracing.clear()


def _context(**options):
    import tuplex_tpu

    return tuplex_tpu.Context({"tuplex.partitionSize": "64KB",
                               "tuplex.sample.maxDetectionRows": "64",
                               **options})


def _write(path, rows, header="a,s,v"):
    with open(path, "w") as fp:
        fp.write(header + "\n")
        fp.writelines(line + "\n" for line in rows)
    return str(path)


def _fixed_rows(n, wide_last=0):
    """Lines of one length (so every file of them cuts at the same rows a
    partition), the last one's string cell `wide_last` bytes wider."""
    rows = [f"{i:06d},{'abcdefgh'[i % 8] * 7},{i % 97:02d}.5" for i in range(n)]
    if wide_last:
        rows[-1] = f"{n - 1:06d},{'w' * (7 + wide_last)},00.5"
    return rows


def _source(ctx, pattern):
    return plan_stages(ctx.csv(pattern)._op, ctx.options_store)[0].source


def _parent_route(ctx, src):
    """The partitions as the parent commit built them: each slice at its own
    widest cell, then one pad pass to the dataset-wide bucketed widths."""
    max_w = ctx.options_store.get_int("tuplex.tpu.maxStrBytes", 4096)
    parts, base = [], 0
    for path in src.files:
        table, bad = src._read_table(path)
        assert not bad
        start = 0
        for m in CS._chunk_sizes(table.num_rows,
                                 CS._csv_rows_per_partition(ctx, table)):
            parts.append(CS._table_to_partition(
                table.slice(start, m), src.schema(), max_w, base + start))
            start += m
        base += table.num_rows
    return C.harmonize_partitions(parts)


def _assert_same_partitions(got, want):
    assert [p.num_rows for p in got] == [p.num_rows for p in want]
    assert [p.start_index for p in got] == [p.start_index for p in want]
    for g, w in zip(got, want):
        assert g.schema == w.schema and list(g.leaves) == list(w.leaves)
        assert (g.normal_mask is None) == (w.normal_mask is None)
        assert g.fallback == w.fallback
        for path, lw in w.leaves.items():
            lg = g.leaves[path]
            assert lg.bytes.shape == lw.bytes.shape, path
            assert lg.bytes.dtype == lw.bytes.dtype
            assert np.array_equal(lg.bytes, lw.bytes), path
            assert lg.lengths.dtype == lw.lengths.dtype
            assert np.array_equal(lg.lengths, lw.lengths)
            assert np.array_equal(lg.valid, lw.valid)


# (a) ----------------------------------------------------------------------
@pytest.mark.parametrize("native", [True, False], ids=["native", "no-native"])
def test_widest_cell_in_the_last_partition_sets_every_partitions_width(
        native, tmp_path, monkeypatch):
    from tuplex_tpu import native as N

    if not native:
        monkeypatch.setenv("TUPLEX_TPU_NO_NATIVE", "1")
        monkeypatch.setattr(N, "_mod", None)
        monkeypatch.setattr(N, "_tried", False)
        assert N.get() is None
    elif N.get() is None:
        pytest.skip("no compiler available")
    ctx = _context()
    src = _source(ctx, _write(tmp_path / "in.csv",
                              _fixed_rows(5000, wide_last=30)))
    stream = src.stream_partitions(ctx)
    assert len(stream.rows) >= 4 and sum(stream.rows) == 5000
    # 6, 37 (the last row's), 4 bytes: in their q8 buckets before any cut
    planned = [leaf.width for leaf in stream.template.leaves.values()]
    assert planned == [8, 40, 8] and stream.template.num_rows == 0
    got = list(stream)
    assert [p.num_rows for p in got] == stream.rows
    for p in got:
        assert [leaf.width for leaf in p.leaves.values()] == planned
    _assert_same_partitions(got, _parent_route(ctx, src))
    # and `load_partitions` is the list of that stream
    _assert_same_partitions(src.load_partitions(ctx), got)


def test_a_cell_over_max_str_bytes_boxes_its_row_as_on_the_parent(tmp_path):
    ctx = _context(**{"tuplex.tpu.maxStrBytes": 20})
    rows = _fixed_rows(3000)
    rows[1700] = f"001700,{'L' * 33},11.5"
    src = _source(ctx, _write(tmp_path / "in.csv", rows))
    got = list(src.stream_partitions(ctx))
    boxed = [(p.start_index + i, v) for p in got
             for i, v in p.fallback.items()]
    assert boxed == [(1700, ("001700", "L" * 33, "11.5"))]
    # the matrix stops at maxStrBytes' bucket; the cell is clamped to 20
    assert {p.leaves["1"].width for p in got} == {24}
    _assert_same_partitions(got, _parent_route(ctx, src))


def test_one_call_cut_equals_the_per_leaf_route_across_chunks(monkeypatch):
    """`cut_strings` works straight off the chunks a slice touches (int32
    or int64 offsets, a slice that starts and ends inside chunks, an empty
    chunk, an over-long cell): the leaves the per-leaf routes build."""
    import pyarrow as pa

    from tuplex_tpu import native as N

    if N.get() is None or not hasattr(N.get(), "cut_strings"):
        pytest.skip("no compiler available")
    cells = [f"{'v' * (i % 11)}{i}" for i in range(900)]
    cells[433] = "LONG" * 9                           # 36 bytes > width 16
    ints = [str(i * 37) for i in range(900)]
    cuts = [0, 200, 200, 611, 900]                    # an empty chunk too
    chunked = [pa.chunked_array([pa.array(v[a:b], t)
                                 for a, b in zip(cuts, cuts[1:])])
               for v, t in ((cells, pa.string()), (ints, pa.large_string()))]
    table = pa.table(chunked, names=["0", "1"])
    schema = T.row_of(["s", "i"], [T.option(T.STR)] * 2)
    widths = [16, 8]
    for start, m in ((0, 900), (150, 500), (201, 3), (610, 290)):
        piece = table.slice(start, m)
        fast = CS._table_to_partition(piece, schema, 4096, start, widths)
        with monkeypatch.context() as mp:
            mp.setattr(CS, "_native_leaves", lambda *a: None)
            per_leaf = CS._table_to_partition(piece, schema, 4096, start,
                                              widths)
            mp.setattr(N, "_mod", None)
            mp.setattr(N, "_tried", True)
            in_numpy = CS._table_to_partition(piece, schema, 4096, start,
                                              widths)
        _assert_same_partitions([fast, fast], [per_leaf, in_numpy])
        boxed = {433 - start: (cells[433], ints[433])} \
            if start <= 433 < start + m else {}
        assert fast.fallback == boxed
        assert C.partition_to_pylist(fast) == \
            list(zip(cells[start:start + m], ints[start:start + m]))


# (b) ----------------------------------------------------------------------
def _two_bucket_rows(ctx, tmp_path):
    """A row count whose six partitions straddle a q8 bucket edge: three of
    edge + 1 rows (the next bucket) and three of edge rows."""
    probe = _source(ctx, _write(tmp_path / "probe.csv", _fixed_rows(4000)))
    cap = CS._csv_rows_per_partition(ctx, probe._read_table(probe.files[0])[0])
    edge = max(b for b in map(C.bucket_size, range(cap // 2, cap)) if b < cap)
    return 6 * edge + 3, [edge + 1] * 3 + [edge] * 3


def test_collect_streams_partitions_cut_on_the_prefetch_thread(
        trace_on, tmp_path):
    ctx = _context()
    n, sizes = _two_bucket_rows(ctx, tmp_path)
    p = _write(tmp_path / "in.csv", _fixed_rows(n, wide_last=9))
    assert _source(ctx, p).stream_partitions(ctx).rows == sizes
    tracing.clear()
    got = ctx.csv(p).map(lambda x: (x["a"] + 1, x["s"], x["v"])).collect()
    evs = tracing.events()
    want = _context(**{"tuplex.tpu.interpretOnly": True}).csv(p) \
        .map(lambda x: (x["a"] + 1, x["s"], x["v"])).collect()
    assert got == want and len(got) == n
    assert got[-1] == (n, "w" * 16, 0.5)

    (job,) = [e for e in evs if e["name"] == "job"]
    names = [e["name"] for e in evs]
    assert "ingest:harmonize" not in names
    assert names.count("ingest:plan-shapes") == 1
    (plan,) = [e for e in evs if e["name"] == "ingest:plan-shapes"]
    assert plan["args"] == {"columns": 3, "partitions": 6,
                            "widths": [8, 16, 8]}
    assert plan["tid"] == job["tid"] and plan["job"] == job["id"]
    # the read: one `ingest` span directly under the job, marked streamed
    ingests = [e for e in evs if e["name"] == "ingest"]
    (read,) = [e for e in ingests if (e["args"] or {}).get("streamed")]
    assert read["parent"] == job["id"] and read["tid"] == job["tid"]
    assert read["args"] == {"streamed": True, "partitions": 6, "rows": n}
    assert plan["parent"] == read["id"]
    # the cuts: the first on the job thread, the rest on the producer's,
    # each under an `ingest` span of its own and naming the job
    cuts = sorted((e for e in evs if e["name"] == "ingest:to-partition"),
                  key=lambda e: e["ts"])
    assert [e["args"]["rows"] for e in cuts] == sizes
    assert cuts[0]["tid"] == job["tid"]
    later = {e["tid"] for e in cuts[1:]}
    assert len(later) == 1 and job["tid"] not in later
    by_id = {e["id"]: e for e in evs}
    for e in cuts:
        assert e["job"] == job["id"]
        pull = by_id[e["parent"]]
        assert pull["name"] == "ingest" and pull["tid"] == e["tid"]
        assert pull["job"] == job["id"] and pull["id"] != read["id"]
    waits = [e for e in evs if e["name"] == "source:wait"]
    assert waits and all(e["tid"] == job["tid"] and e["job"] == job["id"]
                         for e in waits)
    # one trace a distinct batch: the full bucket and the shorter one
    first = [e for e in evs if e["name"] == "dispatch:launch"
             and e["args"]["first_call"] == 1]
    assert len(first) == 2
    assert len([e for e in evs if e["name"] == "dispatch:launch"]) == 6
    ctx.close()


# (c) ----------------------------------------------------------------------
class _Attrs(dict):
    def set(self, key, value):
        self[key] = value
        return self


def _wait_for_pool():
    from tuplex_tpu.exec import compilequeue as CQ

    deadline = time.time() + 180
    while CQ.pending_info()["inflight"] and time.time() < deadline:
        time.sleep(0.05)


def test_precompile_plan_answers_from_the_streams_shapes(tmp_path):
    from tuplex_tpu.api.dataset import _source_partitions

    ctx0 = _context()
    n, sizes = _two_bucket_rows(ctx0, tmp_path)
    p = _write(tmp_path / "in.csv", _fixed_rows(n, wide_last=9))
    seen = {}
    for how in ("stream", "list"):
        # a backend of its own each: what one walk speculated is remembered
        ctx = _context(**{"tuplex.tpu.maxStageOps": 1})
        ds = ctx.csv(p).map(lambda x: (x["a"] * 7 + 3, x["s"], x["v"])) \
            .map(lambda t: t[0] - 11)
        stages = plan_stages(ds._op, ctx.options_store)
        assert len(stages) == 3
        parts = _source_partitions(ctx, stages[0])
        assert isinstance(parts, C.PartitionStream) and parts.rows == sizes
        if how == "list":
            parts = list(parts)
        from tuplex_tpu.exec.local import _avals_spec

        batches = ctx.backend._distinct_batches(parts)
        attrs = _Attrs()
        fut = ctx.backend.precompile_plan(stages, parts, span=attrs)
        if fut is not None:
            fut.result(timeout=180)
        _wait_for_pool()
        seen[how] = ([(_avals_spec(a), s) for a, s in batches], dict(attrs))
        if how == "stream":     # and nothing was built to answer
            assert next(iter(parts)).start_index == 0
    assert seen["stream"] == seen["list"]
    specs, attrs = seen["stream"]
    assert len(specs) == 2                  # full bucket and the shorter one
    assert attrs == {"submitted": 2, "skipped": 0}


# (d) ----------------------------------------------------------------------
def test_two_files_of_different_widths_share_one_width_set(tmp_path):
    ctx = _context()
    _write(tmp_path / "part-0.csv", _fixed_rows(2500))
    _write(tmp_path / "part-1.csv",
           [f"{i:06d},{'z' * 19},{i % 50}" for i in range(2500, 3700)])
    src = _source(ctx, str(tmp_path / "part-*.csv"))
    assert len(src.files) == 2 == len(glob.glob(str(tmp_path / "part-*.csv")))
    stream = src.stream_partitions(ctx)
    got = list(stream)
    assert sum(stream.rows) == 3700 and len(got) == len(stream.rows) >= 4
    assert {tuple(leaf.width for leaf in p.leaves.values()) for p in got} \
        == {(8, 24, 8)}
    assert [p.start_index for p in got] == \
        [sum(stream.rows[:i]) for i in range(len(got))]
    _assert_same_partitions(got, _parent_route(ctx, src))
    rows = ctx.csv(str(tmp_path / "part-*.csv")) \
        .map(lambda x: (x["a"], len(x["s"]))).collect()
    assert rows == [(i, 7) for i in range(2500)] + \
        [(i, 19) for i in range(2500, 3700)]


# (e) ----------------------------------------------------------------------
def test_structurally_bad_rows_keep_their_slots_at_the_planned_widths(
        tmp_path):
    ctx = _context()
    rows = _fixed_rows(4000, wide_last=5)
    rows[10] = "000010,short"                      # a cell missing
    rows[2500] = "002500,abcdefg,12.5,extra,cells"
    src = _source(ctx, _write(tmp_path / "in.csv", rows))
    stream = src.stream_partitions(ctx)
    got = list(stream)
    assert [p.num_rows for p in got] == stream.rows
    assert sum(stream.rows) == 4000
    assert {tuple(leaf.width for leaf in p.leaves.values()) for p in got} \
        == {(8, 16, 8)}
    boxed = {p.start_index + i: v for p in got for i, v in p.fallback.items()}
    assert boxed == {10: ("000010", "short", None),
                     2500: ("002500", "abcdefg", "12.5")}
    want = _context(**{"tuplex.tpu.interpretOnly": True}) \
        .csv(src.files[0]).map(lambda x: (x["a"], x["s"])).collect()
    assert ctx.csv(src.files[0]).map(lambda x: (x["a"], x["s"])).collect() \
        == want
    assert len(want) == 4000 and want[10] == (10, "short")


def test_trailing_bad_rows_take_the_planned_widths_and_a_wider_cell_boxes(
        tmp_path, monkeypatch):
    ctx = _context()
    rows = _fixed_rows(3000)
    rows[5] = "000005,ok"
    rows[2000] = f"002000,{'W' * 40}"              # wider than any good cell
    src = _source(ctx, _write(tmp_path / "in.csv", rows))
    # python's csv disagrees with Arrow about which rows are bad: the
    # positions are lost and the bad rows trail as one partition
    monkeypatch.setattr(CS, "_scan_bad_records", lambda *a, **k: [])
    stream = src.stream_partitions(ctx)
    got = list(stream)
    assert stream.rows[-1] == 2 and sum(stream.rows) == 3000
    assert [p.num_rows for p in got] == stream.rows
    assert {tuple(leaf.width for leaf in p.leaves.values()) for p in got} \
        == {(8, 8, 8)}
    tail = got[-1]
    assert tail.start_index == 2998
    assert tail.normal_mask.tolist() == [True, False]
    assert tail.fallback == {1: ("002000", "W" * 40, None)}
    assert C.partition_to_pylist(tail) == [("000005", "ok", None),
                                           ("002000", "W" * 40, None)]
    got_rows = ctx.csv(src.files[0]) \
        .map(lambda x: (x["a"], x["s"])).collect()
    assert len(got_rows) == 3000
    assert got_rows[-2:] == [(5, "ok"), (2000, "W" * 40)]
    assert got_rows[:5] == [(i, "abcdefgh"[i % 8] * 7) for i in range(5)]


# (f) ----------------------------------------------------------------------
def test_a_source_error_on_the_producer_thread_surfaces_in_collect(
        tmp_path, monkeypatch):
    ctx = _context()
    p = _write(tmp_path / "in.csv", _fixed_rows(5000))
    orig = CS._table_to_partition
    cut_on = []

    def failing(table, schema, max_w, start_index, widths=None):
        cut_on.append(threading.current_thread().name)
        if start_index > 0 and table.num_rows:
            raise OSError("the disk went away under partition "
                          f"{len(cut_on) - 1}")
        return orig(table, schema, max_w, start_index, widths)

    monkeypatch.setattr(CS, "_table_to_partition", failing)
    with pytest.raises(OSError, match="the disk went away"):
        ctx.csv(p).map(lambda x: x["a"] + 1).collect()
    # the template and the first cut are the job thread's; the cut that
    # failed was the producer's
    assert cut_on[-1] == "tuplex-source-prefetch"
    assert "tuplex-source-prefetch" not in cut_on[:-1]


# (g) ----------------------------------------------------------------------
def test_source_prefetch_0_cuts_the_same_partitions_on_the_job_thread(
        trace_on, tmp_path):
    p = _write(tmp_path / "in.csv", _fixed_rows(5000, wide_last=12))
    ctx = _context()
    want = list(_source(ctx, p).stream_partitions(ctx))
    ctx0 = _context(**{"tuplex.tpu.sourcePrefetch": 0})
    _assert_same_partitions(
        list(_source(ctx0, p).stream_partitions(ctx0)), want)
    tracing.clear()
    rows = ctx0.csv(p).map(lambda x: (x["a"], x["s"], x["v"])).collect()
    assert rows == ctx.csv(p) \
        .map(lambda x: (x["a"], x["s"], x["v"])).collect()
    evs = tracing.events()
    job = [e for e in evs if e["name"] == "job"][0]
    in_job0 = [e for e in evs if e["job"] == job["id"]]
    cuts = [e for e in in_job0 if e["name"] == "ingest:to-partition"]
    assert len(cuts) == len(want) >= 4
    assert {e["tid"] for e in cuts} == {job["tid"]}
    assert not [e for e in in_job0
                if e["name"] in ("source:wait", "ingest:harmonize")]


# (h) ----------------------------------------------------------------------
def test_mesh_backend_collects_the_same_rows_from_the_stream(
        trace_on, tmp_path):
    from tuplex_tpu.exec.multihost import MultiHostBackend

    p = _write(tmp_path / "in.csv", _fixed_rows(5000, wide_last=12))
    mesh = _context(**{"tuplex.backend": "multihost",
                       "tuplex.tpu.meshShape": "4"})
    assert isinstance(mesh.backend, MultiHostBackend)
    assert mesh.backend.n_devices == 4
    tracing.clear()
    got = mesh.csv(p).map(lambda x: (x["a"] * 2, x["s"].upper())).collect()
    evs = tracing.events()
    want = _context().csv(p) \
        .map(lambda x: (x["a"] * 2, x["s"].upper())).collect()
    assert got == want and len(got) == 5000
    assert got[-1] == (9998, "W" * 19)
    (job,) = [e for e in evs if e["name"] == "job"]
    assert not [e for e in evs if e["name"] == "ingest:harmonize"]
    cuts = [e for e in evs if e["name"] == "ingest:to-partition"]
    assert len(cuts) >= 4 and all(e["job"] == job["id"] for e in cuts)
    # the backend peeks the first partition on the job thread and chains
    assert [e["tid"] == job["tid"] for e in sorted(
        cuts, key=lambda e: e["ts"])] == [True] + [False] * (len(cuts) - 1)
    mesh.close()


def test_non_csv_sources_are_loaded_and_harmonized_as_before(trace_on):
    ctx = _context()
    data = [(i, "s" * (i % 23)) for i in range(6000)]
    tracing.clear()
    assert ctx.parallelize(data, columns=["a", "s"]) \
        .map(lambda x: (x["a"], len(x["s"]))).collect() \
        == [(i, i % 23) for i in range(6000)]
    names = [e["name"] for e in tracing.events()]
    assert names.count("ingest:harmonize") == 1
    assert "ingest:plan-shapes" not in names
    (read,) = [e for e in tracing.events() if e["name"] == "ingest"]
    assert "streamed" not in read["args"]
