"""CSV ingestion: sniffing, typed device decode, dirty-row dual mode
(reference: test/core Zillow.cc LargeDirtyFileParse + CSVStatistic tests)."""

import os

import pytest


@pytest.fixture()
def csvdir(tmp_path):
    return tmp_path


def write(p, text):
    p.write_text(text)
    return str(p)


def test_sniff_and_collect(ctx, csvdir):
    path = write(csvdir / "a.csv",
                 "id,name,score\n1,alpha,2.5\n2,beta,3.5\n3,gamma,4.0\n")
    ds = ctx.csv(path)
    assert ds.columns == ["id", "name", "score"]
    assert ds.collect() == [(1, "alpha", 2.5), (2, "beta", 3.5),
                            (3, "gamma", 4.0)]


def test_no_header(ctx, csvdir):
    path = write(csvdir / "nh.csv", "1,2\n3,4\n5,6\n")
    ds = ctx.csv(path)
    assert ds.collect() == [(1, 2), (3, 4), (5, 6)]


def test_semicolon_delimiter(ctx, csvdir):
    path = write(csvdir / "s.csv", "a;b\n1;x\n2;y\n")
    ds = ctx.csv(path)
    assert ds.collect() == [(1, "x"), (2, "y")]


def test_dirty_int_column_dual_mode(ctx, csvdir):
    # >=90% clean rows: column speculates to i64; the "oops" row fails the
    # device parse -> general case keeps the string -> x*10 raises TypeError
    # (str*int is actually repetition... use +) -> so use a numeric op
    clean = "\n".join(str(i) for i in range(1, 20))
    path = write(csvdir / "d.csv", f"n\n{clean}\noops\n")
    ds = ctx.csv(path).map(lambda x: x["n"] + 10)
    assert ds.collect() == [i + 10 for i in range(1, 20)]
    assert ds.exception_counts() == {"TypeError": 1}


def test_dirty_with_resolver(ctx, csvdir):
    clean = "\n".join(str(i) for i in range(1, 20))
    path = write(csvdir / "d2.csv", f"n\n{clean}\nbad\n")
    res = (ctx.csv(path)
           .map(lambda x: x["n"] + 1)
           .resolve(TypeError, lambda x: -1)
           .collect())
    assert res == [i + 1 for i in range(1, 20)] + [-1]


def test_below_threshold_column_stays_str(ctx, csvdir):
    # 25% dirty: no specialization pays off; column types as str and the
    # whole job behaves with Python string semantics (reference:
    # normalcaseThreshold semantics, ContextOptions.cc:507)
    path = write(csvdir / "d3.csv", "n\n1\n2\noops\n4\n")
    ds = ctx.csv(path)
    from tuplex_tpu.core import typesys as T

    assert ds.types == [T.STR]
    assert ds.map(lambda x: int(x["n"]) * 10).collect() == [10, 20, 40]


def test_null_values_make_option(ctx, csvdir):
    path = write(csvdir / "nv.csv", "a,b\n1,x\n,y\n3,\n")
    ds = ctx.csv(path)
    rows = ds.collect()
    assert rows == [(1, "x"), (None, "y"), (3, None)]


def test_zillow_mini_pipeline(ctx, csvdir):
    path = write(
        csvdir / "z.csv",
        'title,facts and features,price\n'
        'House For Sale,"3 bds , 2 ba , 1,560 sqft","$350,000"\n'
        'Condo for rent,"2 bds , 1 ba , 800 sqft","$1,200/mo"\n'
        'House For Sale,"4 bds , 3 ba , 2,000 sqft","$500,000"\n'
        'Weird listing,no data,"price on request"\n')

    def extractBd(x):
        val = x["facts and features"]
        i = val.find(" bd")
        if i < 0:
            i = len(val)
        s = val[:i]
        j = s.rfind(",")
        j = 0 if j < 0 else j + 2
        return int(s[j:])

    def extractType(x):
        t = x["title"].lower()
        kind = "unknown"
        if "condo" in t or "apartment" in t:
            kind = "condo"
        if "house" in t:
            kind = "house"
        return kind

    ds = (ctx.csv(path)
          .withColumn("bedrooms", extractBd)
          .filter(lambda x: x["bedrooms"] < 10)
          .withColumn("type", extractType)
          .filter(lambda x: x["type"] == "house")
          .selectColumns(["title", "bedrooms"]))
    assert ds.collect() == [("House For Sale", 3), ("House For Sale", 4)]
    # the weird row died at extractBd with ValueError
    assert ds.exception_counts() == {"ValueError": 1}


def test_multifile_glob(ctx, csvdir):
    write(csvdir / "p1.csv", "x\n1\n2\n")
    write(csvdir / "p2.csv", "x\n3\n4\n")
    ds = ctx.csv(str(csvdir / "p*.csv"))
    assert sorted(ds.collect()) == [1, 2, 3, 4]


def test_tocsv_roundtrip(ctx, csvdir):
    src = write(csvdir / "r.csv", "a,b\n1,x\n2,y\n")
    outp = str(csvdir / "out.csv")
    ctx.csv(src).mapColumn("a", lambda v: v * 10).tocsv(outp)
    ds2 = ctx.csv(outp)
    assert ds2.collect() == [(10, "x"), (20, "y")]


def test_text_source(ctx, csvdir):
    path = write(csvdir / "t.txt", "hello\nworld\nfoo\n")
    res = ctx.text(path).map(lambda s: s.upper()).collect()
    assert res == ["HELLO", "WORLD", "FOO"]


def test_type_hints(ctx, csvdir):
    path = write(csvdir / "th.csv", "a\n1\n2\n")
    from tuplex_tpu.core import typesys as T

    ds = ctx.csv(path, type_hints={0: T.option(T.F64)})
    assert ds.collect() == [1.0, 2.0]


def test_select_by_index_with_pushdown(ctx, csvdir):
    # regression: int selections must survive projection pruning
    path = write(csvdir / "pi.csv", "a,b,junk\n1,x,9\n2,y,8\n")
    assert ctx.csv(path).selectColumns([0, -2]).collect() == [(1, "x"), (2, "y")]


def test_pushdown_with_segmentation(ctx, csvdir):
    # review regression: segmentation must inherit the pruned projection
    import re as _re

    path = write(csvdir / "seg.csv", "a,b,c\n1,100,7\n2,200,8\n3,300,9\n")
    ds = (ctx.csv(path)
          .withColumn("d", lambda x: x["a"] + x["c"])
          .filter(lambda x: _re.match("x", "y") is None)   # not compilable
          .selectColumns(["a", "c", "d"]))
    assert ds.collect() == [(1, 7, 8), (2, 8, 10), (3, 9, 12)]


def test_pushdown_keeps_map_resolver_columns(ctx, csvdir):
    path = write(csvdir / "res.csv", "a,b\n1,10\n0,20\n3,30\n")
    ds = (ctx.csv(path)
          .map(lambda x: 100 // x["a"])
          .resolve(ZeroDivisionError, lambda x: x["b"]))
    assert ds.collect() == [100, 20, 33]


def test_csv_user_columns_override_with_projection(ctx, tmp_path):
    # ADVICE r1 (medium): with header=True + user-overridden column names,
    # projection pushdown keyed Arrow include_columns by the user names while
    # the table was read under the FILE's header names -> ArrowKeyError.
    p = tmp_path / "o.csv"
    p.write_text("colA,colB,colC\n1,x,10\n2,y,20\n3,z,30\n")
    ds = ctx.csv(str(p), columns=["a", "b", "c"], header=True)
    # subset-reading UDF triggers projection pushdown into the Arrow read
    got = ds.map(lambda r: r["c"]).collect()
    assert got == [10, 20, 30]
    # no-projection path: cells must still be read as strings then decoded
    got2 = sorted(ctx.csv(str(p), columns=["a", "b", "c"],
                          header=True).collect())
    assert got2 == [(1, "x", 10), (2, "y", 20), (3, "z", 30)]


def test_malformed_rows_merge_in_order(ctx, csvdir):
    # ADVICE r1 (low): structurally-invalid rows must come back at their
    # ORIGINAL positions (reference merge-in-order), not as a trailing blob
    path = write(csvdir / "m.csv",
                 "a,b\n1,x\n2,y,EXTRA\n3,z\n4,w,E,F\n5,v\n")
    got = ctx.csv(path).map(lambda r: r["a"]).collect()
    # bad rows (2 and 4) box through the fallback path; their first cell
    # still parses as the normal-case i64 via the interpreter
    assert got == [1, 2, 3, 4, 5]


def test_nulls_in_sample_are_normal_case(ctx, tmp_path):
    # nulls observed in the sample speculate the column to Option[i64]: they
    # decode on the FAST path, no violation at all
    p = tmp_path / "g.csv"
    rows = [("" if i % 13 == 0 else str(i)) + ",k" for i in range(2000)]
    p.write_text("n,t\n" + "\n".join(rows) + "\n")
    ds = ctx.csv(str(p)).map(lambda x: 0 if x["n"] is None else x["n"] * 2)
    assert ds.collect() == [0 if i % 13 == 0 else i * 2
                            for i in range(2000)]


def test_general_case_tier_string_widening(ctx, tmp_path):
    # VERDICT r1 next#4: mixed int/str column below the junk threshold:
    # normal=i64 (majority), general=str. Violating rows must resolve on the
    # COMPILED general tier — zero per-row python.
    import tuplex_tpu.exec.local as LB

    p = tmp_path / "m.csv"
    rows = ["x" + str(i) if i % 11 == 0 else str(i) for i in range(2000)]
    p.write_text("v\n" + "\n".join(rows) + "\n")

    interp_rows = {"n": 0}
    orig = LB.C.decode_rows

    def counting(part, indices):
        out = orig(part, indices)
        interp_rows["n"] += len(out)
        return out

    LB.C.decode_rows = counting
    try:
        got = ctx.csv(str(p)).map(lambda x: len(str(x["v"]))).collect()
    finally:
        LB.C.decode_rows = orig
    want = [len(("x" + str(i)) if i % 11 == 0 else str(i))
            for i in range(2000)]
    assert got == want
    # all ~182 violating rows resolved on the compiled general tier
    assert interp_rows["n"] == 0, interp_rows


def test_projection_through_aggregate_boundary(tmp_path):
    """r4: the aggregate breaker's reads (keys + UDF row subscripts) narrow
    the upstream stage's source projection — dead columns stop being
    decoded; parity holds on the compiled AND interpreter paths."""
    import tuplex_tpu
    from tuplex_tpu.plan.physical import plan_stages

    path = tmp_path / "wide.csv"
    rows = [(i % 3, f"g{i % 4}", i * 1.5, i * 2.0, f"dead{i}", i)
            for i in range(400)]
    with open(path, "w") as fp:
        fp.write("k1,k2,v1,deadf,deads,v2\n")
        for r in rows:
            fp.write(",".join(map(str, r)) + "\n")

    def agg(a, x):
        return (a[0] + x["v1"], a[1] + x["v2"])

    c = tuplex_tpu.Context()
    ds = (c.csv(str(path))
          .filter(lambda x: x["k1"] != 99)
          .aggregateByKey(lambda a, b: (a[0] + b[0], a[1] + b[1]),
                          agg, (0.0, 0), ["k2"]))
    stages = plan_stages(ds._op, c.options_store)
    st0 = stages[0]
    assert st0.source_projection is not None
    assert set(st0.source_projection) == {"k1", "k2", "v1", "v2"}, \
        st0.source_projection
    assert "deads" not in (st0.output_columns or ())

    want = {}
    for k1, k2, v1, deadf, deads, v2 in rows:
        a = want.get(k2, (0.0, 0))
        want[k2] = (a[0] + v1, a[1] + v2)
    got = dict((k, (a, b)) for k, a, b in
               [(r[0], r[1], r[2]) for r in ds.collect()])
    assert set(got) == set(want)
    for k in want:
        assert abs(got[k][0] - want[k][0]) < 1e-9
        assert got[k][1] == want[k][1]

    # interpreter path: same plan, forced off-device (exercises the
    # zero-row/pruned-schema alignment the review flagged)
    c2 = tuplex_tpu.Context({"tuplex.tpu.interpretOnly": True})
    ds2 = (c2.csv(str(path))
           .filter(lambda x: x["k1"] != 99)
           .aggregateByKey(lambda a, b: (a[0] + b[0], a[1] + b[1]),
                           agg, (0.0, 0), ["k2"]))
    got2 = sorted(map(repr, ds2.collect()))
    got1 = sorted(map(repr, ds.collect()))
    assert got1 == got2


def test_chunk_sizes_balanced():
    # balanced splitting: no tiny tail partition (its fixed dispatch cost
    # dwarfs its rows), empty input yields no chunks
    from tuplex_tpu.io.csvsource import _chunk_sizes

    assert _chunk_sizes(0, 1000) == []
    assert _chunk_sizes(-5, 1000) == []
    assert _chunk_sizes(500, 1000) == [500]
    assert _chunk_sizes(1000, 1000) == [1000]
    assert _chunk_sizes(1250, 1000) == [1250]        # absorbed tail (+25%)
    got = _chunk_sizes(2600, 1000)                   # balanced, not 1000+1000+600
    assert sum(got) == 2600 and len(got) == 3
    assert max(got) - min(got) <= 1
    got = _chunk_sizes(101350, 100000)
    assert got == [101350]
