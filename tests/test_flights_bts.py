"""The flights deployment at the source's 110 columns (PR 35), on the CPU
at a small size: the planner's column sets on the 110-column plan and on
TPC-H Q19 (projection through joins); the pipeline of
`bench/configs/flights-bts/flights.py` through `Context` against that
file's plain CPython reference on three seeds; a left join's `None` fill
and a build side with two rows a key against a dict join; the generator,
the two controls, the two new readers and the `BENCHMARK.json` entries.

On XLA:CPU graphlint's `wide-str-compaction` veto (for XLA:CPU alone)
sends the source stage to the interpreter tier, so the answer is held
here and the tier on the chip (`bench/run.py` counts it there)."""

import ast
import csv
import importlib.util
import inspect
import json
import os
import random
import string
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench")
CONFIG_DIR = os.path.join(BENCH, "configs", "flights-bts")
CELL = "flights-bts.cancelled2"
ROWS = 1500
PARAMS = {"year": 2019, "month": 7, "cancelled": 0.019, "diverted": 0.0025,
          "delay_causes_filled": 0.19, "unknown_airport": 0.03,
          "airport_zipf": 1.0, "carrier_zipf": 0.7}
SIZES = {"flights": ROWS, "carriers": 200, "airports": 372}
LIMITS = {"rows_missing_or_extra": 0, "rows_differ": 0,
          "float_rel_gap": 1e-12}


def _load(path, name):
    """A file of the benchmark by path, registered in `sys.modules` so that
    the program's reflection finds the UDFs' source."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


GEN = _load(os.path.join(CONFIG_DIR, "generate.py"), "flights_bts_generate")
FL = _load(os.path.join(CONFIG_DIR, "flights.py"), "flights_bts_flights")
COLUMNS = GEN.COLUMNS


@pytest.fixture(autouse=True)
def _as_on_the_chip(monkeypatch):
    """On the chip the compile queue never forks; hold XLA:CPU to that."""
    monkeypatch.setenv("TUPLEX_COMPILE_ISOLATION", "thread")


def tables(seed, sizes=SIZES, **over):
    params = dict(PARAMS, _rows=dict(sizes), **over)
    return {t: GEN.gen_chunk(t, random.Random(f"{seed}:{t}:0"), n, 0, params)
            for t, n in sizes.items()}


def write(tmp_path, tabs):
    paths = {}
    for t, rows in tabs.items():
        paths[t] = str(tmp_path / f"{t}.csv")
        with open(paths[t], "w", newline="") as fp:
            w = csv.writer(fp)
            w.writerow(COLUMNS[t])
            w.writerows(rows)
    return paths


def side_of(tabs):
    return {t: {"columns": COLUMNS[t], "rows": tabs[t]}
            for t in ("carriers", "airports")}


def reference(tabs, control=False):
    return FL.reference_merge(
        [FL.reference_partial(COLUMNS["flights"], tabs["flights"],
                              side_of(tabs), control)], control)


def failing(compared):
    return {name for name, value, limit in compared if not value <= limit}


# ---- the planner: projection through joins ----

def _plan(ctx, ds):
    from tuplex_tpu.plan.physical import plan_stages

    return plan_stages(getattr(ds, "_op", ds), ctx.options_store)


def test_the_110_column_plan_reads_30_columns_and_no_join_carries_more(
        tmp_path, ctx):
    from tuplex_tpu.plan.physical import JoinStage, TransformStage
    from tuplex_tpu.runtime import tracing

    paths = write(tmp_path, tables(5, dict(SIZES, flights=300)))
    was = tracing.enabled()
    tracing.enable(True)
    t0 = tracing.now_us()
    try:
        stages = _plan(ctx, FL.build(ctx, paths))
        proj = [e["args"] for e in tracing.events_since(t0)
                if e["name"] == "plan:projection"]
    finally:
        tracing.enable(was)
    assert proj[-1] == {"sources": 1, "file_columns": 110,
                        "kept_columns": 30, "joins_crossed": 3}
    source = next(s for s in stages if getattr(s, "source", None))
    read = source.source_projection
    assert len(read) == 30 and len(COLUMNS["flights"]) == 110
    assert {FL._camel(c) for c in read} == set(USED)
    joins = [s for s in stages if isinstance(s, JoinStage)]
    assert [j.op.how for j in joins] == ["inner", "left", "left"]
    # a join's row: the 30 live columns of the flights side, then three of
    # the carrier and three of each airport side
    assert [len(j.output_schema.columns) for j in joins] == [33, 36, 39]
    last = [s for s in stages if isinstance(s, TransformStage)][-1]
    assert list(last.output_schema.columns) == FL.OUTPUT_COLS
    assert set(joins[-1].output_schema.columns) == {
        "OriginLongitudeDecimal" if c == "OriginLongitude" else
        "OriginLatitudeDecimal" if c == "OriginLatitude" else
        "DestLongitudeDecimal" if c == "DestLongitude" else
        "DestLatitudeDecimal" if c == "DestLatitude" else
        "OpUniqueCarrier" if c == "CarrierCode" else
        "OpCarrierFlNum" if c == "FlightNumber" else
        "DayOfMonth" if c == "Day" else
        "AirlineName" if c == "CarrierName" else
        "Origin" if c == "OriginAirportIATACode" else
        "Dest" if c == "DestAirportIATACode" else c
        for c in FL.OUTPUT_COLS}
    # the build sides: the carrier file has two columns and both are read;
    # of the airport file's 16, the key and the three that are selected
    # (the two capwords columns are dead, and dropped with their operators)
    sides = [_plan(ctx, j.op.right) for j in joins]
    assert [getattr(s[0], "source_projection", None) for s in sides] == [
        None, ["IATACode", "Altitude", "LatitudeDecimal",
               "LongitudeDecimal"]] + [
        ["IATACode", "Altitude", "LatitudeDecimal", "LongitudeDecimal"]]
    assert [list(s[-1].output_schema.columns) for s in sides][0] == [
        "Code", "AirlineName", "AirlineYearFounded", "AirlineYearDefunct"]


USED = ["Year", "Month", "DayOfMonth", "DayOfWeek", "OpUniqueCarrier",
        "OpCarrierFlNum", "Origin", "OriginCityName", "Dest", "DestCityName",
        "CrsDepTime", "CrsArrTime", "CrsElapsedTime", "ActualElapsedTime",
        "AirTime", "Distance", "Cancelled", "CancellationCode", "Diverted",
        "DivReachedDest", "DivActualElapsedTime", "ArrDelay", "DepDelay",
        "CarrierDelay", "WeatherDelay", "NasDelay", "SecurityDelay",
        "LateAircraftDelay", "TaxiIn", "TaxiOut"]


def test_q19_source_stage_reads_six_columns(tmp_path, ctx):
    q19 = _load(os.path.join(BENCH, "configs", "tpch-sf033", "q19.py"),
                "flights_bts_q19")
    gen = _load(os.path.join(BENCH, "configs", "tpch-sf033", "generate.py"),
                "flights_bts_tpch_generate")
    params = {"_rows": {"lineitem": 400, "part": 200}}
    paths = {}
    for t, n in params["_rows"].items():
        paths[t] = str(tmp_path / f"{t}.csv")
        with open(paths[t], "w", newline="") as fp:
            w = csv.writer(fp)
            w.writerow(gen.COLUMNS[t])
            w.writerows(gen.gen_chunk(t, random.Random(f"3:{t}:0"), n, 0,
                                      params))
    stages = _plan(ctx, q19.build(ctx, paths))
    assert stages[0].source_projection == [
        "l_partkey", "l_quantity", "l_extendedprice", "l_discount",
        "l_shipinstruct", "l_shipmode"]
    # the filters have run by the join: its row holds what the predicate
    # and the sum read, and the part side the key and three columns
    assert list(stages[1].output_schema.columns) == [
        "l_quantity", "l_extendedprice", "l_discount", "l_partkey",
        "p_brand", "p_size", "p_container"]
    (side,) = _plan(ctx, stages[1].op.right)
    assert side.source_projection == ["p_partkey", "p_brand", "p_size",
                                      "p_container"]


@pytest.mark.parametrize("udf, kept", [
    (lambda x: x.upper() if x else None, False),
    (lambda x: string.capwords(x.strip()) if x else None, False),
    # each of these raises on some string or on its argument: the row goes
    (lambda x: x.format("a") if x else None, True),
    (lambda x: x.center("a") if x else None, True),
    (lambda x: x.replace("a") if x else None, True),
    (lambda x: x.strip(1) if x else None, True),
    (lambda x: string.capwords(x, 1) if x else None, True),
    (lambda x: x.upper(), True),             # None has no upper()
], ids=["upper", "capwords", "format", "center", "replace-1", "strip-int",
        "capwords-2", "unguarded"])
def test_only_a_dead_operator_that_cannot_raise_is_dropped(
        tmp_path, ctx, udf, kept):
    from tuplex_tpu.plan.optimizer import _total_when_guarded

    right = str(tmp_path / "r.csv")
    with open(right, "w") as fp:
        fp.write("k,city\n" + "".join(
            f"{i},{'{1}' if i == 3 else '' if i == 4 else f'c{i}'}\n"
            for i in range(64)))
    op = ctx.csv(right).mapColumn("city", udf)._op
    assert _total_when_guarded(op) is not kept


def test_a_dead_operator_that_can_raise_is_kept(tmp_path, ctx):
    """`int(x)` raises on a cell that is no number, and CPython would drop
    that row: the operator stays though nothing reads its result, and so
    does the column it reads; so does `format`, which raises on "{1}"."""
    left, right = str(tmp_path / "l.csv"), str(tmp_path / "r.csv")
    with open(left, "w") as fp:
        fp.write("k,a,b\n" + "".join(f"{i % 7},x{i},{i}\n"
                                     for i in range(200)))
    with open(right, "w") as fp:
        fp.write("k,n,name,city\n" + "".join(
            f"{i},{i if i != 3 else 'three'},n{i},c{i}\n" for i in range(7)))
    r = (ctx.csv(right)
         .mapColumn("n", lambda x: int(x))
         .mapColumn("city", lambda x: x.upper() if x else None))
    ds = ctx.csv(left).join(r, "k", "k").selectColumns(["a", "name"])
    stages = _plan(ctx, ds)
    assert stages[0].source_projection == ["k", "a"]
    (side,) = _plan(ctx, stages[1].op.right)
    assert side.source_projection == ["k", "n", "name"]   # not `city`
    got = ds.collect()
    assert len(got) == sum(1 for i in range(200) if i % 7 != 3)
    r = (ctx.csv(right)
         .mapColumn("city", lambda x: x.format("a") if x else None))
    ds = ctx.csv(left).join(r, "k", "k").selectColumns(["a", "name"])
    side = _plan(ctx, _plan(ctx, ds)[1].op.right)
    assert side[0].source_projection == ["k", "name", "city"]


# ---- one plan for one distribution: nothing turns on a single row ----

def test_the_plan_does_not_turn_on_the_seed(tmp_path):
    """A diverted flight (0.25%), one that reached its destination, a
    scheduled arrival at midnight (0.09%): each is in the head of some
    files and not of others, and none of them may change a stage's key, a
    schema or the general-case types (a changed plan is a compile of
    minutes on the chip)."""
    import tuplex_tpu
    from tuplex_tpu.plan.physical import TransformStage

    at = COLUMNS["flights"].index
    plans, heads = set(), set()
    sizes = {"flights": 1500, "carriers": 1900, "airports": 1500}
    for seed in range(1, 9):
        tabs = tables(seed, sizes)
        head = tabs["flights"][:690]            # what the planner samples
        heads.add((any(r[at("DIVERTED")] == "1.00" for r in head),
                   any(r[at("DIV_REACHED_DEST")] == "1.00" for r in head),
                   any(r[at("CRS_ARR_TIME")] == "0000" for r in head)))
        ctx = tuplex_tpu.Context({"tuplex.tpu.compileDeadlineS": 900})
        try:
            stages = _plan(ctx, FL.build(ctx, write(tmp_path, tabs)))
            plans.add(tuple(
                (s.key(), s.input_schema.name, s.output_schema.name,
                 getattr(s.ops[0], "general", None)
                 and s.ops[0].general.name)
                if isinstance(s, TransformStage) else s.output_schema.name
                for s in stages))
        finally:
            ctx.close()
    assert len(heads) >= 4          # the seeds do differ in what they show
    assert len(plans) == 1


def test_an_arm_is_pruned_on_a_share_of_enough_trials():
    from tuplex_tpu.compiler.branchprof import (ARM_SHARE, MIN_TRIALS,
                                                observed)

    assert observed(0, MIN_TRIALS - 1) == (True, True)   # too few trials
    assert observed(0, 700) == (False, True)
    assert observed(2, 698) == (False, True)             # 0.3%: not seen
    assert observed(8, 700) == (True, True) and ARM_SHARE == 0.01
    assert observed(698, 2) == (True, False)
    # an arm that is not worth pruning is observed whatever it did
    assert observed(0, 700, (False, True)) == (True, True)


def test_a_rare_none_does_not_make_a_udf_output_an_option(tmp_path, ctx):
    """One `None` among a thousand results is a deviant row, not the normal
    case: the column's type does not turn on it, and the row's answer is
    CPython's all the same."""
    fmt = lambda x: "{:02}".format(x) if x else None     # noqa: E731
    got = {}
    for name, zeros in (("none", ()), ("one", (500,)),
                        ("fifth", range(0, 2000, 5))):
        path = str(tmp_path / f"{name}.csv")
        vals = [0 if i in set(zeros) else i % 97 + 1 for i in range(2000)]
        with open(path, "w") as fp:
            fp.write("a,b\n" + "".join(f"{v},x\n" for v in vals))
        ds = ctx.csv(path).mapColumn("a", fmt)
        got[name] = ds._op.schema().types[0].name
        assert ds.collect() == [(fmt(v), "x") for v in vals]
    assert got == {"none": "str", "one": "str", "fifth": "Option[str]"}


def test_the_general_case_reads_windows_from_all_over_the_file(tmp_path):
    """The head of the file holds integers alone; one cell in a hundred
    behind it is a float. The normal case stays the head's, the general
    case names the float whichever rows the head shows."""
    import tuplex_tpu

    path = str(tmp_path / "t.csv")
    with open(path, "w") as fp:
        fp.write("a,b\n" + "".join(
            f"{i},{i}.5\n" if i > 30000 and i % 100 == 0 else f"{i},{i}\n"
            for i in range(60000)))
    ctx = tuplex_tpu.Context({"tuplex.csv.maxDetectionMemory": "16KB"})
    try:
        op = ctx.csv(path)._op
        while not hasattr(op, "general"):
            op = op.parent
        assert os.path.getsize(path) > 17 * 16384        # windows, not all
        assert [t.name for t in op.declared.types] == ["i64", "i64"]
        assert [t.name for t in op.general.types] == ["i64", "f64"]
        assert len(op.parent.stat.sample_rows) <= 1000
    finally:
        ctx.close()


# ---- the joins against a dict join ----

def test_left_join_fills_none_and_two_rows_a_key_against_a_dict_join(
        tmp_path, ctx, spans):
    rng = random.Random(11)
    left = [(f"f{i}", rng.choice("ABCDEX"), i) for i in range(400)]
    carriers = [("A", "a1", 1), ("B", "b1", 2), ("A", "a2", 3),
                ("C", "c1", 4), ("D", "d1", 5), ("B", "b2", 6)]
    airports = [("A", 1.5), ("C", 2.5), ("E", 3.5)]
    lp, cp, ap = (str(tmp_path / n) for n in ("l.csv", "c.csv", "a.csv"))
    for path, head, rows in ((lp, "name,code,n", left),
                             (cp, "Code,Desc,rank", carriers),
                             (ap, "Key,Lat", airports)):
        with open(path, "w", newline="") as fp:
            fp.write(head + "\n")
            csv.writer(fp).writerows(rows)
    ds = (ctx.csv(lp)
          .join(ctx.csv(cp), "code", "Code")
          .leftJoin(ctx.csv(ap), "code", "Key", prefixes=(None, "Ap")))
    got = [tuple(r) for r in ds.collect()]
    by_code: dict = {}
    for c, d, r in carriers:
        by_code.setdefault(c, []).append((d, r))
    lat = dict(airports)
    want = [(name, n, d, r, code, lat.get(code))
            for name, code, n in left for d, r in by_code.get(code, ())]
    assert got == want                       # in the probe side's order
    assert sum(1 for w in want if w[-1] is None) > 50
    inner, outer = spans("join:execute")
    assert "filled" not in inner["args"]
    assert inner["args"]["columns_in"] == 3
    assert inner["args"]["columns_out"] == 5
    assert outer["args"]["columns_out"] == 6
    assert outer["args"]["filled"] == sum(1 for w in want if w[-1] is None)
    assert all(g["args"]["columns"] in (5, 6) for g in spans("join:gather"))
    reads = {e["args"]["file_columns"]: e["args"]["columns"]
             for e in spans("ingest:read-csv")}
    assert reads == {3: 3, 2: 2}


@pytest.mark.parametrize("keyless", [1, 8], ids=["boxed", "option"])
def test_a_build_side_with_keyless_rows_stays_on_the_vector_path(
        tmp_path, ctx, spans, keyless):
    """An airport the database gives no code: as an Option key (8 of 24
    rows) or, under the normal-case threshold (1 of 24), as a row the
    general tier hands over boxed. Neither equals a probe key, and neither
    sends the join to the row-wise dict; the probe's key comes out of an
    earlier join at its own width."""
    rng = random.Random(12)
    codes = [f"A{c}" for c in "BCDEFGHIJKLMNOPQ"]
    left = [(f"f{i}", rng.choice(codes + ["ZZ"]), i) for i in range(500)]
    names = [(c, f"n{c}") for c in codes + ["ZZ"]]
    airports = [(c, 1.5 + i) for i, c in enumerate(codes)]
    airports[3:3] = [("N/A", 90.0 + k) for k in range(keyless)]
    lp, np_, ap = (str(tmp_path / n) for n in ("l.csv", "n.csv", "a.csv"))
    for path, head, rows in ((lp, "name,code,n", left),
                             (np_, "Code,Name", names),
                             (ap, "Key,Lat", airports)):
        with open(path, "w", newline="") as fp:
            fp.write(head + "\n")
            csv.writer(fp).writerows(rows)
    ds = (ctx.csv(lp).join(ctx.csv(np_), "code", "Code")
          .leftJoin(ctx.csv(ap, null_values=["", "N/A"]), "code", "Key",
                    prefixes=(None, "Ap")))
    got = [tuple(r) for r in ds.collect()]
    lat = dict(a for a in airports if a[0] != "N/A")
    assert got == [(name, n, "n" + code, code, lat.get(code))
                   for name, code, n in left]
    assert sum(1 for g in got if g[-1] is None) > 10
    assert len(spans("join:assemble")) == 2
    assert not spans("join:build-table")


@pytest.fixture()
def spans():
    from tuplex_tpu.runtime import tracing

    was = tracing.enabled()
    tracing.enable(True)
    tracing.clear()
    yield lambda name: [e for e in tracing.events_since(0)
                        if e["name"] == name and e.get("dur") is not None]
    tracing.enable(was)


# ---- the pipeline against the plain reference ----

@pytest.mark.parametrize("seed", [7, 35, 4000000035])
def test_the_pipeline_equals_the_reference(tmp_path, spans, seed):
    import tuplex_tpu

    tabs = tables(seed)
    want = reference(tabs)
    assert len(want) > ROWS * 0.9
    i = FL.OUTPUT_COLS.index
    assert 20 < sum(1 for w in want if w[i("OriginAltitude")] is None) < 120
    ctx = tuplex_tpu.Context({"tuplex.tpu.compileDeadlineS": 900})
    try:
        got = FL.build(ctx, write(tmp_path, tabs)).collect()
    finally:
        ctx.close()
    compared = FL.compare(got, want, LIMITS)
    assert not failing(compared), compared
    (read,) = [e for e in spans("ingest:read-csv")
               if e["args"]["file_columns"] == 110]
    assert read["args"]["columns"] == 30
    assert sorted(e["args"]["columns_out"]
                  for e in spans("join:execute")) == [33, 36, 39]
    # an airport the database lacks: the left joins fill None
    assert sorted(e["args"]["filled"] for e in spans("join:execute")
                  if "filled" in e["args"]) == sorted(
        sum(1 for w in want if w[i(c)] is None)
        for c in ("OriginLatitude", "DestLatitude"))


# ---- the generator ----

def test_the_generator_is_a_function_of_the_seed():
    a, b, c = tables(3), tables(3), tables(4)
    assert a == b and a["flights"] != c["flights"]
    assert a["carriers"] != c["carriers"]
    # the codes are the row's alone: every seed's flights find them
    assert [r[0] for r in a["carriers"]] == [r[0] for r in c["carriers"]]
    assert [r[1] for r in a["airports"]][:360] == \
        [r[1] for r in c["airports"]][:360]


def test_110_columns_in_bts_order():
    cols = COLUMNS["flights"]
    assert len(cols) == 110 == len(set(cols))
    assert cols[:11] == ["YEAR", "QUARTER", "MONTH", "DAY_OF_MONTH",
                         "DAY_OF_WEEK", "FL_DATE", "OP_UNIQUE_CARRIER",
                         "OP_CARRIER_AIRLINE_ID", "OP_CARRIER", "TAIL_NUM",
                         "OP_CARRIER_FL_NUM"]
    assert cols[11:20] == ["ORIGIN_AIRPORT_ID", "ORIGIN_AIRPORT_SEQ_ID",
                           "ORIGIN_CITY_MARKET_ID", "ORIGIN",
                           "ORIGIN_CITY_NAME", "ORIGIN_STATE_ABR",
                           "ORIGIN_STATE_FIPS", "ORIGIN_STATE_NM",
                           "ORIGIN_WAC"]
    assert cols[20:29] == [c.replace("ORIGIN", "DEST") for c in cols[11:20]]
    assert cols[29] == "CRS_DEP_TIME" and cols[40] == "CRS_ARR_TIME"
    assert cols[47:50] == ["CANCELLED", "CANCELLATION_CODE", "DIVERTED"]
    assert cols[56:61] == ["CARRIER_DELAY", "WEATHER_DELAY", "NAS_DELAY",
                           "SECURITY_DELAY", "LATE_AIRCRAFT_DELAY"]
    assert cols[64:69] == ["DIV_AIRPORT_LANDINGS", "DIV_REACHED_DEST",
                           "DIV_ACTUAL_ELAPSED_TIME", "DIV_ARR_DELAY",
                           "DIV_DISTANCE"]
    assert cols[69:77] == ["DIV1_AIRPORT", "DIV1_AIRPORT_ID",
                           "DIV1_AIRPORT_SEQ_ID", "DIV1_WHEELS_ON",
                           "DIV1_TOTAL_GTIME", "DIV1_LONGEST_GTIME",
                           "DIV1_WHEELS_OFF", "DIV1_TAIL_NUM"]
    assert cols[101] == "DIV5_AIRPORT" and cols[108] == "DIV5_TAIL_NUM"
    assert len(COLUMNS["airports"]) == 16 and \
        COLUMNS["carriers"] == ["Code", "Description"]
    from tuplex_tpu.models import flights as model

    assert COLUMNS["airports"] == model.AIRPORT_COLS
    assert {FL._camel(c) for c in cols} >= set(USED)
    assert all(len(r) == 110 and r[109] == ""
               for r in tables(1, dict(SIZES, flights=200))["flights"])


def test_the_null_shares_are_the_stated_ones_within_a_standard_deviation():
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as fp:
        cell = json.load(fp)
    n = cell["test_rows"]
    sizes = {"flights": n, "carriers": 200, "airports": 372}
    rows = tables(21, sizes, **cell["params"])["flights"]
    at = COLUMNS["flights"].index
    p = cell["params"]

    def share(pred):
        return sum(1 for r in rows if pred(r)) / n

    def near(got, want):
        return abs(got - want) <= (want * (1 - want) / n) ** 0.5

    empty = lambda c: (lambda r: r[at(c)] == "")       # noqa: E731
    assert near(share(lambda r: r[at("CANCELLED")] == "1.00"),
                p["cancelled"])
    assert near(share(lambda r: r[at("DIVERTED")] == "1.00"), p["diverted"])
    for c in ("DIV_REACHED_DEST",):
        assert near(share(empty(c)), 1 - p["diverted"])
    for c in ("CARRIER_DELAY", "WEATHER_DELAY", "NAS_DELAY",
              "SECURITY_DELAY", "LATE_AIRCRAFT_DELAY"):
        assert near(share(empty(c)), 1 - p["delay_causes_filled"])
    for c in ("ARR_DELAY", "AIR_TIME", "ACTUAL_ELAPSED_TIME"):
        assert near(share(empty(c)), p["cancelled"] + p["diverted"])
    assert near(share(empty("DEP_DELAY")), p["cancelled"])
    for c in ("ORIGIN", "DEST"):
        assert near(share(lambda r: r[at(c)][0] == "Z"),
                    p["unknown_airport"])
    # a cause is a number only where the flight arrived 15 minutes late
    assert all((r[at("CARRIER_DELAY")] != "") ==
               (r[at("ARR_DELAY")] != "" and float(r[at("ARR_DELAY")]) >= 15)
               for r in rows)
    assert {r[at("CANCELLATION_CODE")] for r in rows
            if r[at("CANCELLED")] == "1.00"} == set("ABCD")
    reached = [r[at("DIV_REACHED_DEST")] for r in rows
               if r[at("DIVERTED")] == "1.00"]
    assert set(reached) == {"1.00", "0.00"}
    assert all((r[at("DIV_ACTUAL_ELAPSED_TIME")] != "") ==
               (r[at("DIV_REACHED_DEST")] == "1.00") for r in rows)
    # the head of a file is like the rest of it: the rare rows are drawn
    # uniformly over a chunk, the codes at random, midnight is 0000
    div = [i for i, r in enumerate(rows) if r[at("DIVERTED")] == "1.00"]
    assert len(div) == round(n * p["diverted"])
    assert 0.1 < sum(1 for i in div if i < n // 4) / len(div) < 0.4
    assert len({r[at("CANCELLATION_CODE")] for r in rows[:n // 4]
                if r[at("CANCELLED")] == "1.00"}) == 4
    assert [r[at("CANCELLATION_CODE")] for r in rows
            if r[at("CANCELLED")] == "1.00"][:8] != list("ABCDABCD")
    assert any(r[at("CRS_ARR_TIME")] == "0000" for r in rows)
    # the side tables: one code in ten twice, N/A and empty as nulls
    full = tables(21, {"flights": 200, "carriers": 1900, "airports": 9300})
    codes = [r[0] for r in full["carriers"]]
    assert len(codes) == 1900 and len(set(codes)) == 1900 - 1900 // 11
    assert sum(1 for r in full["airports"] if r[1] == "N/A") > 500
    assert {len(r) for r in full["airports"]} == {16}


# ---- `correct` and its two controls ----

@pytest.mark.parametrize("seed", [7, 35, 4000000035])
def test_both_controls_fail_and_the_true_answer_passes(seed):
    tabs = tables(seed, dict(SIZES, flights=3000))
    want = reference(tabs)
    assert not failing(FL.compare(list(want), want, LIMITS))
    inner = reference(tabs, "inner")
    assert failing(FL.compare(inner, want, LIMITS)) == {
        "rows_missing_or_extra"}
    assert 100 < len(want) - len(inner) < 300
    f32 = FL.compare(reference(tabs, "float32"), want, LIMITS)
    assert failing(f32) == {"float_rel_gap"}
    assert 1e-9 < dict((n, v) for n, v, _ in f32)["float_rel_gap"] < 1e-6
    both = reference(tabs, True)          # what `run.py --control` runs
    assert failing(FL.compare(both, want, LIMITS)) == {
        "rows_missing_or_extra", "float_rel_gap"}
    # a wrong type, a None for a value and a lost row are each seen
    i = FL.OUTPUT_COLS.index
    broken = [list(w) for w in want]
    broken[5][i("Cancelled")] = 0
    broken[9][i("OriginLatitude")] = None
    assert dict((n, v) for n, v, _ in FL.compare(
        broken, want, LIMITS))["rows_differ"] == 2
    assert dict((n, v) for n, v, _ in FL.compare(
        want[:40] + want[41:], want, LIMITS)) == {
        "rows_missing_or_extra": 1, "rows_differ": 0, "float_rel_gap": 0.0}
    assert FL.answer_bytes(want[:1]) == sum(
        len(v.encode()) if isinstance(v, str) else 8 for v in want[0])


# ---- the two new readers ----

def _reader(name):
    return _load(os.path.join(BENCH, "layer_metrics", name + ".py"),
                 "flights_bts_reader_" + name)


def _span(name, args):
    return {"name": name, "cat": "io", "ts": 0.0, "dur": 1e6, "tid": 1,
            "depth": 2, "id": None, "parent": None, "job": None,
            "args": args}


def test_the_new_readers_on_recorded_spans_and_on_a_program_without_them():
    kept, cols = _reader("source_columns_kept_share"), \
        _reader("join_row_columns")
    spans = [_span("ingest:read-csv", {"bytes": 900, "rows": 5,
                                       "columns": 30, "file_columns": 110}),
             _span("ingest:read-csv", {"bytes": 100, "rows": 5,
                                       "columns": 4, "file_columns": 16}),
             _span("join:execute", {"rows_out": 5, "columns_in": 30,
                                    "columns_out": 33}),
             _span("join:execute", {"rows_out": 5, "columns_in": 33,
                                    "columns_out": 39, "filled": 2})]
    run = {"window": {"spans": spans}}
    assert kept.read(run) == pytest.approx(
        100 * (0.9 * 30 / 110 + 0.1 * 4 / 16))
    assert cols.read(run) == 36.0
    # the parent's spans: `columns` alone, `rows_out` alone
    old = {"window": {"spans": [
        _span("ingest:read-csv", {"bytes": 900, "rows": 5, "columns": 110}),
        _span("join:execute", {"rows_out": 5})]}}
    assert kept.read(old) is None and cols.read(old) is None
    assert kept.read({"window": {"spans": []}}) is None
    assert cols.read({"window": {"spans": []}}) is None


# ---- the entries ----

def test_the_benchmark_entries_name_files_that_exist():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fp:
        bm = json.load(fp)
    cfg = bm["configs"][-1]
    assert cfg["name"] == "flights-bts" and cfg["reduced"] == ["tables"]
    with open(os.path.join(REPO, cfg["file"])) as fp:
        stated = json.load(fp)
    assert cfg["source"] == stated["source"] and len(cfg["source"]) <= 200
    assert stated["reduced"] == ["tables"]
    assert stated["context_options"] == {"tuplex.tpu.compileDeadlineS": 900}
    assert stated["limits"] == LIMITS
    for key in ("deployment", "guarantees", "reduced_why", "assumed"):
        assert stated[key], key
    assert {t: v["rows"] for t, v in stated["tables"].items()} == {
        "flights": 400000, "carriers": 1900, "airports": 9300}
    cell = bm["workloads"][-1]
    assert cell == {"name": CELL, "config": "flights-bts",
                    "traffic": "cancelled2", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    assert sum(1 for w in bm["workloads"] if w["chips"] == 4) == 1
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as fp:
        traffic = json.load(fp)
    assert traffic["config"] == "flights-bts"
    assert traffic["context_options"] == {}
    assert os.path.isfile(os.path.join(CONFIG_DIR,
                                       traffic["pipeline"] + ".py"))
    assert os.path.isfile(os.path.join(CONFIG_DIR, "generate.py"))
    for name in ("FACT", "SIDE", "build", "reference_partial",
                 "reference_merge", "compare", "answer_bytes"):
        assert hasattr(FL, name), name
    by_name = {m["name"]: m for m in bm["per_layer"]}
    names = [m["name"] for m in bm["per_layer"]]
    at = names.index("source_columns_kept_share")
    assert names[at: at + 2] == [
        "source_columns_kept_share", "join_row_columns"]
    assert by_name["source_columns_kept_share"] == {
        "name": "source_columns_kept_share", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "ingest", "moves": "rows_per_s",
        "workloads": [CELL]}
    assert by_name["join_row_columns"] == {
        "name": "join_row_columns", "unit": "count", "better": "lower",
        "source": "program_span", "layer": "agg/join",
        "moves": "rows_per_s", "workloads": [CELL]}
    for m in bm["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py")), m["name"]
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL, m["name"]
    for n in ("agg_host_share", "agg_device_fold_share", "prewarm_hit_share",
              "shard_imbalance", "mesh_put_share", "sharded_fetch_share"):
        assert CELL not in by_name[n]["workloads"], n


# ---- the benchmark's copy of the script against the model's ----

def _body(src):
    """A function's statements, without its docstring."""
    stmts = ast.parse(textwrap.dedent(src)).body[0].body
    if isinstance(stmts[0], ast.Expr) and \
            isinstance(stmts[0].value, ast.Constant):
        stmts = stmts[1:]
    return [ast.dump(st) for st in stmts]


def test_the_benchmarks_udfs_are_the_models_word_for_word():
    from tuplex_tpu.models import flights as model

    for name in ("cleanCode", "divertedUDF", "fillInTimesUDF",
                 "extractDefunctYear"):
        assert inspect.getsource(getattr(FL, name)) == \
            inspect.getsource(getattr(model, name)), name
    assert FL.NUMERIC_COLS == model.NUMERIC_COLS
    assert FL.OUTPUT_COLS == model.OUTPUT_COLS
    # the script: the model's, but for the airport file's read (a comma
    # CSV with a header here, ':'-separated and headerless there)
    mine = inspect.getsource(FL.build).replace(
        'ctx.csv(paths["airports"], null_values=AIRPORT_NULLS)', "AIRPORTS")
    theirs = inspect.getsource(model.build_pipeline).replace(
        'ctx.csv(airport_path, columns=AIRPORT_COLS, delimiter=":",\n'
        '                          header=False, null_values=["", "N/a", '
        '"N/A"])', "AIRPORTS")
    for a, b in (('paths["flights"]', "perf_path"),
                 ('paths["carriers"]', "carrier_path")):
        mine = mine.replace(a, b)
    # and for the two statements that refuse a tree without the planner pass
    assert [st for st in _body(mine) if "optimizer" not in st] == \
        _body(theirs)
    assert sum("optimizer" in st for st in _body(mine)) == 2


def test_the_script_refuses_a_tree_without_the_planner_pass(
        tmp_path, ctx, monkeypatch):
    from tuplex_tpu.plan import optimizer

    paths = write(tmp_path, tables(5, dict(SIZES, flights=200)))
    monkeypatch.delattr(optimizer, "project_through_joins")
    with pytest.raises(RuntimeError, match="projection through joins"):
        FL.build(ctx, paths)


def test_the_models_generator_takes_the_110_column_schema(tmp_path):
    from tuplex_tpu.models import flights as model

    narrow, wide = str(tmp_path / "n.csv"), str(tmp_path / "w.csv")
    model.generate_perf_csv(narrow, 50, seed=2)
    model.generate_perf_csv(wide, 50, seed=2, columns=COLUMNS["flights"])
    with open(narrow, newline="") as fp:
        n = list(csv.DictReader(fp))
    with open(wide, newline="") as fp:
        w = list(csv.DictReader(fp))
    assert list(n[0]) == model.PERF_COLS and len(n[0]) == 30
    assert list(w[0]) == COLUMNS["flights"]
    # the 30 columns the script reads hold the same cells; the other 80
    # are there and empty
    for a, b in zip(n, w):
        assert {k.upper(): v for k, v in a.items()} == {
            k: v for k, v in b.items() if k.lower() in a}
        assert {v for k, v in b.items() if k.lower() not in a} == {""}
