"""The flights pipeline (Tuplex, SIGMOD'21, section 6.1; upstream
`benchmarks/flights/runtuplex.py`): the pipeline asked of the program, its
plain CPython reference and the comparison that decides `correct`.

The UDFs are the published cleaning logic, as `tuplex_tpu/models/flights.py`
has them, over the 110 columns of a BTS on-time file: every column renamed
to CamelCase, the city/state splits, the time formats, the cancellation
codes, an inner join with the carrier history on the carrier code (more
than one row a code: the defunct-year filter drops the stale match), two
left joins with the airport database (origin and destination, prefixed,
`None` for an airport the database lacks), twelve delay columns cast to
int, 39 columns selected. The reference below runs the same logic row by
row over `csv` rows and imports nothing of the program.
"""

from __future__ import annotations

FACT = "flights"
SIDE = ("carriers", "airports")
AIRPORT_NULLS = ["", "N/a", "N/A"]


def cleanCode(t):
    if t["CancellationCode"] == "A":
        return "carrier"
    elif t["CancellationCode"] == "B":
        return "weather"
    elif t["CancellationCode"] == "C":
        return "national air system"
    elif t["CancellationCode"] == "D":
        return "security"
    else:
        return None


def divertedUDF(row):
    diverted = row["Diverted"]
    ccode = row["CancellationCode"]
    if diverted:
        return "diverted"
    else:
        if ccode:
            return ccode
        else:
            return "None"


def fillInTimesUDF(row):
    ACTUAL_ELAPSED_TIME = row["ActualElapsedTime"]
    if row["DivReachedDest"]:
        if float(row["DivReachedDest"]) > 0:
            return float(row["DivActualElapsedTime"])
        else:
            return ACTUAL_ELAPSED_TIME
    else:
        return ACTUAL_ELAPSED_TIME


def extractDefunctYear(t):
    x = t["Description"]
    desc = x[x.rfind("-") + 1: x.rfind(")")].strip()
    return int(desc) if len(desc) > 0 else None


NUMERIC_COLS = ["ActualElapsedTime", "AirTime", "ArrDelay", "CarrierDelay",
                "CrsElapsedTime", "DepDelay", "LateAircraftDelay", "NasDelay",
                "SecurityDelay", "TaxiIn", "TaxiOut", "WeatherDelay"]

OUTPUT_COLS = ["CarrierName", "CarrierCode", "FlightNumber", "Day", "Month",
               "Year", "DayOfWeek", "OriginCity", "OriginState",
               "OriginAirportIATACode", "OriginLongitude", "OriginLatitude",
               "OriginAltitude", "DestCity", "DestState",
               "DestAirportIATACode", "DestLongitude", "DestLatitude",
               "DestAltitude", "Distance", "CancellationReason", "Cancelled",
               "Diverted", "CrsArrTime", "CrsDepTime", "ActualElapsedTime",
               "AirTime", "ArrDelay", "CarrierDelay", "CrsElapsedTime",
               "DepDelay", "LateAircraftDelay", "NasDelay", "SecurityDelay",
               "TaxiIn", "TaxiOut", "WeatherDelay", "AirlineYearFounded",
               "AirlineYearDefunct"]
FLOAT_COLS = ("OriginLongitude", "OriginLatitude", "DestLongitude",
              "DestLatitude", "Distance")
_FLOAT_AT = tuple(OUTPUT_COLS.index(c) for c in FLOAT_COLS)


def build(ctx, paths: dict):
    """The source's script over `ctx.csv(flights)`, not yet collected."""
    import string

    from tuplex_tpu.plan import optimizer

    # A tree whose planner does not project through joins decodes all 110
    # columns: on the chip its general tier alone compiles for more than
    # 900 s and no first job ends in 1,288 s (PERF.md section 6, PR 35,
    # call 1). Such a tree fails here, at once, and not at a run's clock.
    if not hasattr(optimizer, "project_through_joins"):
        raise RuntimeError("flights-bts needs projection through joins "
                           "(plan/optimizer.project_through_joins)")
    df = ctx.csv(paths["flights"])
    renamed =["".join(w.capitalize() for w in c.split("_"))
               for c in df.columns]
    for i, c in enumerate(list(df.columns)):
        df = df.renameColumn(c, renamed[i])

    df_airports = ctx.csv(paths["airports"], null_values=AIRPORT_NULLS)
    df_carrier = ctx.csv(paths["carriers"])

    df = df.withColumn(
        "OriginCity",
        lambda x: x["OriginCityName"][: x["OriginCityName"].rfind(",")].strip())
    df = df.withColumn(
        "OriginState",
        lambda x: x["OriginCityName"][x["OriginCityName"].rfind(",") + 1:].strip())
    df = df.withColumn(
        "DestCity",
        lambda x: x["DestCityName"][: x["DestCityName"].rfind(",")].strip())
    df = df.withColumn(
        "DestState",
        lambda x: x["DestCityName"][x["DestCityName"].rfind(",") + 1:].strip())
    df = df.mapColumn(
        "CrsArrTime",
        lambda x: "{:02}:{:02}".format(int(x / 100), x % 100) if x else None)
    df = df.mapColumn(
        "CrsDepTime",
        lambda x: "{:02}:{:02}".format(int(x / 100), x % 100) if x else None)
    df = df.withColumn("CancellationCode", cleanCode)
    df = df.mapColumn("Diverted", lambda x: True if x > 0 else False)
    df = df.mapColumn("Cancelled", lambda x: True if x > 0 else False)
    df = df.withColumn("CancellationReason", divertedUDF)
    df = df.withColumn("ActualElapsedTime", fillInTimesUDF).ignore(TypeError)

    df_carrier = df_carrier.withColumn(
        "AirlineName",
        lambda x: x["Description"][: x["Description"].rfind("(")].strip())
    df_carrier = df_carrier.withColumn(
        "AirlineYearFounded",
        lambda x: int(x["Description"][x["Description"].rfind("(") + 1:
                                       x["Description"].rfind("-")]))
    df_carrier = df_carrier.withColumn("AirlineYearDefunct",
                                       extractDefunctYear)

    df_airports = df_airports.mapColumn(
        "AirportName", lambda x: string.capwords(x) if x else None)
    df_airports = df_airports.mapColumn(
        "AirportCity", lambda x: string.capwords(x) if x else None)

    df_all = df.join(df_carrier, "OpUniqueCarrier", "Code")
    df_all = df_all.leftJoin(df_airports, "Origin", "IATACode",
                             prefixes=(None, "Origin"))
    df_all = df_all.leftJoin(df_airports, "Dest", "IATACode",
                             prefixes=(None, "Dest"))

    df_all = df_all.mapColumn("Distance", lambda x: x / 0.00062137119224)
    df_all = df_all.mapColumn(
        "AirlineName",
        lambda s: s.replace("Inc.", "").replace("LLC", "")
        .replace("Co.", "").strip())
    df_all = (df_all
              .renameColumn("OriginLongitudeDecimal", "OriginLongitude")
              .renameColumn("OriginLatitudeDecimal", "OriginLatitude")
              .renameColumn("DestLongitudeDecimal", "DestLongitude")
              .renameColumn("DestLatitudeDecimal", "DestLatitude")
              .renameColumn("OpUniqueCarrier", "CarrierCode")
              .renameColumn("OpCarrierFlNum", "FlightNumber")
              .renameColumn("DayOfMonth", "Day")
              .renameColumn("AirlineName", "CarrierName")
              .renameColumn("Origin", "OriginAirportIATACode")
              .renameColumn("Dest", "DestAirportIATACode"))

    def filterDefunctFlights(row):
        year = row["Year"]
        airlineYearDefunct = row["AirlineYearDefunct"]
        if airlineYearDefunct:
            return int(year) < int(airlineYearDefunct)
        else:
            return True

    df_all = df_all.filter(filterDefunctFlights)
    for c in NUMERIC_COLS:
        df_all = df_all.mapColumn(c, lambda x: int(x) if x else 0)
    return df_all.selectColumns(OUTPUT_COLS)


# ---------------------------------------------------------------------------
# the plain reference: `csv` rows in, tuples out
# ---------------------------------------------------------------------------

def _camel(c: str) -> str:
    return "".join(w.capitalize() for w in c.split("_"))


def _carrier_table(side: dict) -> dict:
    """code -> its history entries, in file order (a code may have two)."""
    table = side["carriers"].get("_by_code")
    if table is None:
        table = side["carriers"]["_by_code"] = {}
        cols = side["carriers"]["columns"]
        for r in side["carriers"]["rows"]:
            x = dict(zip(cols, r))
            d = x["Description"]
            name = d[: d.rfind("(")].strip()
            founded = int(d[d.rfind("(") + 1: d.rfind("-")])
            desc = d[d.rfind("-") + 1: d.rfind(")")].strip()
            table.setdefault(x["Code"], []).append(
                (name, founded, int(desc) if len(desc) > 0 else None))
    return table


def _airport_table(side: dict) -> dict:
    """IATA code -> the rows of that code: (longitude, latitude, altitude).
    A field the database writes as empty or N/A is None, and a row without
    a code is one no flight can match."""
    table = side["airports"].get("_by_code")
    if table is None:
        table = side["airports"]["_by_code"] = {}
        cols = side["airports"]["columns"]
        at = [cols.index(c) for c in ("IATACode", "LongitudeDecimal",
                                      "LatitudeDecimal", "Altitude")]
        for r in side["airports"]["rows"]:
            code, lon, lat, alt = (None if r[i] in AIRPORT_NULLS else r[i]
                                   for i in at)
            if code is not None:
                table.setdefault(code, []).append(
                    (None if lon is None else float(lon),
                     None if lat is None else float(lat),
                     None if alt is None else int(alt)))
    return table


def _opt_float(cell: str):
    return float(cell) if cell != "" else None


def _hhmm(t: int):
    return "{:02}:{:02}".format(int(t / 100), t % 100) if t else None


def reference_partial(columns: list, rows: list, side: dict,
                      control=False) -> list:
    """The script in plain CPython over one chunk; a row whose UDF raises
    is dropped, as the program drops a row no tier can resolve. `control`
    breaks a guarantee: "inner" runs the airport joins as inner joins,
    "float32" folds `Distance` in float32, True does both."""
    import numpy as np

    inner = control in (True, "inner")
    fold32 = control in (True, "float32")
    carriers, airports = _carrier_table(side), _airport_table(side)
    at = {_camel(c): i for i, c in enumerate(columns)}
    out = []
    for rec in rows:
        try:
            x = {k: rec[i] for k, i in at.items()}
            ocn, dcn = x["OriginCityName"], x["DestCityName"]
            x["OriginCity"] = ocn[: ocn.rfind(",")].strip()
            x["OriginState"] = ocn[ocn.rfind(",") + 1:].strip()
            x["DestCity"] = dcn[: dcn.rfind(",")].strip()
            x["DestState"] = dcn[dcn.rfind(",") + 1:].strip()
            x["CrsArrTime"] = _hhmm(int(x["CrsArrTime"]))
            x["CrsDepTime"] = _hhmm(int(x["CrsDepTime"]))
            code = {"A": "carrier", "B": "weather",
                    "C": "national air system",
                    "D": "security"}.get(x["CancellationCode"])
            x["Diverted"] = True if float(x["Diverted"]) > 0 else False
            x["Cancelled"] = True if float(x["Cancelled"]) > 0 else False
            x["CancellationReason"] = "diverted" if x["Diverted"] \
                else (code if code else "None")
            elapsed = _opt_float(x["ActualElapsedTime"])
            if x["DivReachedDest"] and float(x["DivReachedDest"]) > 0:
                try:
                    elapsed = float(_opt_float(x["DivActualElapsedTime"]))
                except TypeError:       # .ignore(TypeError): the row goes
                    continue
            x["ActualElapsedTime"] = elapsed
            for c in NUMERIC_COLS[1:]:
                x[c] = _opt_float(x[c])
            dist = float(x["Distance"])
            x["Distance"] = float(np.float32(dist)
                                  / np.float32(0.00062137119224)) \
                if fold32 else dist / 0.00062137119224
            year = int(x["Year"])
            for c in NUMERIC_COLS:
                x[c] = int(x[c]) if x[c] else 0
            x.update(CarrierCode=x["OpUniqueCarrier"],
                     FlightNumber=int(x["OpCarrierFlNum"]),
                     Day=int(x["DayOfMonth"]), Month=int(x["Month"]),
                     Year=year, DayOfWeek=int(x["DayOfWeek"]),
                     OriginAirportIATACode=x["Origin"],
                     DestAirportIATACode=x["Dest"])
            none = [] if inner else [(None, None, None)]
            for name, founded, defunct in carriers.get(
                    x["OpUniqueCarrier"], ()):
                if defunct and not year < defunct:
                    continue
                x.update(CarrierName=name.replace("Inc.", "")
                         .replace("LLC", "").replace("Co.", "").strip(),
                         AirlineYearFounded=founded,
                         AirlineYearDefunct=defunct)
                for o in airports.get(x["Origin"], none):
                    (x["OriginLongitude"], x["OriginLatitude"],
                     x["OriginAltitude"]) = o
                    for d in airports.get(x["Dest"], none):
                        (x["DestLongitude"], x["DestLatitude"],
                         x["DestAltitude"]) = d
                        out.append(tuple(x[c] for c in OUTPUT_COLS))
        except Exception:       # the UDF's own error: the row is dropped
            continue
    return out


def reference_merge(partials: list, control=False) -> list:
    """Chunks in input order: a join keeps its probe side's order."""
    return [row for part in partials for row in part]


def _exact(row) -> tuple:
    """The columns that are compared exactly, types included."""
    return tuple((type(v), v) for i, v in enumerate(row)
                 if i not in _FLOAT_AT or not isinstance(v, float))


def compare(got: list, want: list, limits: dict) -> list:
    """(name, value, limit), rows in input order. Where rows are missing
    (or extra), the longer answer's rows that the other lacks are stepped
    over, as many as the answers differ by, so that a missing row is one
    missing row and not a difference in every row after it. A row differs
    if a column that is not a float differs, by value or by type (`None`
    against a float too); floats by their widest relative gap."""
    got = [tuple(r) for r in got]
    short, long_ = (got, want) if len(got) <= len(want) else (want, got)
    spare = len(long_) - len(short)
    j = differ = 0
    gap = 0.0
    for s in short:
        key = _exact(s)
        while spare and _exact(long_[j]) != key:
            j, spare = j + 1, spare - 1
        other = long_[j]
        j += 1
        if _exact(other) != key:
            differ += 1
            continue
        for i in _FLOAT_AT:
            if isinstance(s[i], float) and s[i] != other[i]:
                w = (other if long_ is want else s)[i]
                gap = max(gap, abs(s[i] - other[i]) / max(abs(w), 1e-300))
    return [("rows_missing_or_extra", abs(len(got) - len(want)),
             limits["rows_missing_or_extra"]),
            ("rows_differ", differ, limits["rows_differ"]),
            ("float_rel_gap", gap, limits["float_rel_gap"])]


def answer_bytes(answer: list) -> int:
    """Bytes of the answer as a program must hand it over: string bytes as
    UTF-8 and 8 bytes for every other cell."""
    n = 0
    for row in answer:
        for v in row:
            n += len(v.encode()) if isinstance(v, str) else 8
    return n
