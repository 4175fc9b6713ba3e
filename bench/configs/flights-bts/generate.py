"""Synthetic BTS "Reporting Carrier On-Time Performance" files at the
source's 110 columns, with the two side tables of the flights pipeline:
`L_CARRIER_HISTORY` and the GlobalAirportDatabase (16 fields).

None of the three files is here, so every shape below is from memory of
the paper and of BTS and is listed under `assumed` in `flights-bts.json`.
One call makes one chunk of one table from its own `random.Random`, so a
chunk is the same whatever process makes it. Which rows are cancelled,
diverted, late or fly from an airport the database lacks is drawn by
*count* within a chunk (`round(n * share)` rows), so a table's shares are
the cell's parameters to within a rounding, and uniformly over the chunk:
the head of a file is like the rest of it. The side
tables are functions of the row index (names and years from the chunk's
generator), so the flights chunks know every code without reading them.
"""

from __future__ import annotations

import datetime
import itertools

_SIDES = ("ORIGIN", "DEST")
_DIV = ["AIRPORT", "AIRPORT_ID", "AIRPORT_SEQ_ID", "WHEELS_ON",
        "TOTAL_GTIME", "LONGEST_GTIME", "WHEELS_OFF", "TAIL_NUM"]

FLIGHT_COLUMNS = (
    ["YEAR", "QUARTER", "MONTH", "DAY_OF_MONTH", "DAY_OF_WEEK", "FL_DATE",
     "OP_UNIQUE_CARRIER", "OP_CARRIER_AIRLINE_ID", "OP_CARRIER", "TAIL_NUM",
     "OP_CARRIER_FL_NUM"]
    + [f"{s}{c}" for s in _SIDES for c in (
        "_AIRPORT_ID", "_AIRPORT_SEQ_ID", "_CITY_MARKET_ID", "",
        "_CITY_NAME", "_STATE_ABR", "_STATE_FIPS", "_STATE_NM", "_WAC")]
    + ["CRS_DEP_TIME", "DEP_TIME", "DEP_DELAY", "DEP_DELAY_NEW", "DEP_DEL15",
       "DEP_DELAY_GROUP", "DEP_TIME_BLK", "TAXI_OUT", "WHEELS_OFF",
       "WHEELS_ON", "TAXI_IN", "CRS_ARR_TIME", "ARR_TIME", "ARR_DELAY",
       "ARR_DELAY_NEW", "ARR_DEL15", "ARR_DELAY_GROUP", "ARR_TIME_BLK",
       "CANCELLED", "CANCELLATION_CODE", "DIVERTED", "CRS_ELAPSED_TIME",
       "ACTUAL_ELAPSED_TIME", "AIR_TIME", "FLIGHTS", "DISTANCE",
       "DISTANCE_GROUP", "CARRIER_DELAY", "WEATHER_DELAY", "NAS_DELAY",
       "SECURITY_DELAY", "LATE_AIRCRAFT_DELAY", "FIRST_DEP_TIME",
       "TOTAL_ADD_GTIME", "LONGEST_ADD_GTIME", "DIV_AIRPORT_LANDINGS",
       "DIV_REACHED_DEST", "DIV_ACTUAL_ELAPSED_TIME", "DIV_ARR_DELAY",
       "DIV_DISTANCE"]
    + [f"DIV{k}_{c}" for k in range(1, 6) for c in _DIV]
    # the files end every line with a comma: an empty, unnamed 110th column
    + ["UNNAMED_109"])

AIRPORT_COLUMNS = ["ICAOCode", "IATACode", "AirportName", "AirportCity",
                   "Country", "LatitudeDegrees", "LatitudeMinutes",
                   "LatitudeSeconds", "LatitudeDirection",
                   "LongitudeDegrees", "LongitudeMinutes",
                   "LongitudeSeconds", "LongitudeDirection", "Altitude",
                   "LatitudeDecimal", "LongitudeDecimal"]

COLUMNS = {"flights": FLIGHT_COLUMNS,
           "carriers": ["Code", "Description"],
           "airports": AIRPORT_COLUMNS}

_A = "ABCDEFGHIJKLMNOPQRSTUVWXYZ"
_STATES = [("MA", "25", "Massachusetts", "13"), ("NY", "36", "New York", "22"),
           ("CA", "06", "California", "91"), ("IL", "17", "Illinois", "41"),
           ("TX", "48", "Texas", "74"), ("GA", "13", "Georgia", "34"),
           ("WA", "53", "Washington", "93"), ("CO", "08", "Colorado", "82"),
           ("FL", "12", "Florida", "33"), ("NC", "37", "North Carolina", "36")]
_CITY_1 = ["Boston", "New York", "Los Angeles", "Chicago", "Dallas/Fort Worth",
           "Atlanta", "Seattle", "Denver", "Orlando", "Charlotte",
           "Salt Lake City", "St. Louis", "Raleigh/Durham", "West Palm Beach",
           "Minneapolis", "Sioux Falls", "Bend/Redmond", "Hilo",
           "Washington", "Kansas City"]
_NAME_1 = ["american", "pan", "trans", "pacific", "northern", "southern",
           "eastern", "western", "united", "national", "continental",
           "midway", "frontier", "island", "mountain", "coastal", "sky",
           "alaska", "hawaiian", "republic", "golden", "great lakes"]
_NAME_2 = ["air lines", "airways", "airlines", "air", "express", "aviation",
           "air service", "air cargo", "international", "jet"]
_SUFFIX = ["Inc.", "Inc.", "Inc.", "Co.", "LLC", "", "", "Corp."]
_AP_WORDS = ["general", "edward", "lawrence", "logan", "john", "kennedy",
             "o'hare", "hartsfield", "jackson", "regional", "municipal",
             "county", "field", "memorial", "metropolitan", "city", "lake",
             "valley", "st", "fort", "san", "las", "new", "north"]
_COUNTRIES = ["USA", "USA", "USA", "USA", "CANADA", "MEXICO", "GERMANY",
              "PAPUA NEW GUINEA", "BRAZIL", "AUSTRALIA", "JAPAN"]
REPORTING_CARRIERS = 17
FLOWN_AIRPORTS = 360


def carrier_code(k: int) -> str:
    """Code `k` of L_CARRIER_HISTORY: two characters (a letter, then a
    letter or a digit), then three, all distinct."""
    if k < 26 * 36:
        return _A[k // 36] + (_A + "0123456789")[k % 36]
    k -= 26 * 36
    return _A[k // 676 % 26] + _A[k // 26 % 26] + _A[k % 26]


def carrier_row_code(row: int) -> tuple:
    """(code index, stale) of row `row` of the carrier table: every
    eleventh row is a second, defunct entry of a code ten rows up (code
    index 3, 13, 23 ...), so one code in ten has two rows."""
    g, o = divmod(row, 11)
    return (10 * g + 3, True) if o == 10 else (10 * g + o, False)


def airport_iata(row: int) -> str:
    """IATA code of row `row` of the airport table: three letters, the
    first never 'Z' (codes in 'Z..' are the ones the database lacks)."""
    j = (row * 7919 + 13) % 16900
    return _A[j // 676] + _A[j // 26 % 26] + _A[j % 26]


def zipf_cum(n: int, s: float) -> list:
    w = [1.0 / (r + 1) ** s for r in range(n)]
    return list(itertools.accumulate(w))


def _pick(rng, n_rows: int, share: float, taken: set) -> set:
    """`round(n_rows * share)` rows of the chunk that `taken` lacks."""
    free = [i for i in range(n_rows) if i not in taken]
    return set(rng.sample(free, min(len(free), round(n_rows * share))))


def gen_chunk(table: str, rng, n_rows: int, first_row: int,
              params: dict) -> list:
    """`n_rows` rows of `table` as lists of strings, in COLUMNS order."""
    if table == "flights":
        return _flights(rng, n_rows, params)
    if table == "carriers":
        return [_carrier(rng, first_row + i, params) for i in range(n_rows)]
    if table == "airports":
        return [_airport(rng, first_row + i) for i in range(n_rows)]
    raise ValueError(f"flights-bts has no table {table!r}")


def _carrier(rng, row: int, params: dict) -> list:
    k, stale = carrier_row_code(row)
    year = int(params["year"])
    name = f"{rng.choice(_NAME_1)} {rng.choice(_NAME_2)}".title()
    if rng.random() < 0.1:
        name = name.replace(" ", "-", 1)
    suffix = rng.choice(_SUFFIX)
    if suffix:
        name += " " + suffix
    founded = rng.randint(1919, year - 12)
    if stale:                            # the stale entry of a live code
        span = f"{founded} - {rng.randint(founded + 1, year - 10)}"
    elif k < REPORTING_CARRIERS:         # flown this month: not yet defunct
        span = f"{founded} - {year + 3}" if k == 7 else f"{founded} - "
    elif rng.random() < 0.6:             # most of the history is defunct
        span = f"{founded} - {rng.randint(founded + 1, year - 1)}"
    else:
        span = f"{founded} - "
    return [carrier_code(k), f"{name} ({span})"]


def _airport(rng, row: int) -> list:
    iata = airport_iata(row)
    # the database gives no IATA code to one field in twelve, beyond the
    # airports that flights use
    key = "N/A" if row >= FLOWN_AIRPORTS and rng.random() < 1 / 12 else iata
    name = " ".join(rng.choice(_AP_WORDS) for _ in range(rng.randint(1, 4)))
    city = " ".join(rng.choice(_AP_WORDS) for _ in range(rng.randint(1, 2)))
    lat = rng.uniform(-60.0, 72.0)
    lon = rng.uniform(-179.0, 179.0)
    vals = [("K" + iata) if row < FLOWN_AIRPORTS or rng.random() < 0.5
            else "".join(rng.choice(_A) for _ in range(4)),
            key,
            "N/A" if rng.random() < 0.02 else name,
            "N/A" if rng.random() < 0.02 else city,
            rng.choice(_COUNTRIES)]
    for v, pos, neg in ((lat, "N", "S"), (lon, "E", "W")):
        a = abs(v)
        vals += [f"{int(a):03d}", f"{int(a * 60) % 60:02d}",
                 f"{int(a * 3600) % 60:02d}", pos if v >= 0 else neg]
    alt = "" if rng.random() < 0.01 else str(rng.randint(-20, 4300))
    return vals + [alt, f"{lat:.3f}", f"{lon:.3f}"]


def _num(v) -> str:
    """A BTS measure: two decimals ("-5.00", "1.00")."""
    return f"{v:.2f}"


def _hhmm(minutes: int) -> str:
    """A clock time as four digits."""
    minutes %= 1440
    return f"{minutes // 60:02d}{minutes % 60:02d}"


def _blk(minutes: int) -> str:
    h = minutes % 1440 // 60
    return "0001-0559" if h < 6 else f"{h:02d}00-{h:02d}59"


def _flights(rng, n_rows: int, params: dict) -> list:
    year, month = int(params["year"]), int(params["month"])
    sizes = params["_rows"]
    n_air = min(FLOWN_AIRPORTS, int(sizes["airports"]))
    n_car = min(REPORTING_CARRIERS, int(sizes["carriers"]) * 10 // 11)
    air_cum = zipf_cum(n_air, float(params["airport_zipf"]))
    car_cum = zipf_cum(n_car, float(params["carrier_zipf"]))
    days = (datetime.date(year + month // 12, month % 12 + 1, 1)
            - datetime.date(year, month, 1)).days
    cancelled = _pick(rng, n_rows, float(params["cancelled"]), set())
    diverted = _pick(rng, n_rows, float(params["diverted"]), cancelled)
    late = _pick(rng, n_rows, float(params["delay_causes_filled"]),
                 cancelled | diverted)
    unknown = [_pick(rng, n_rows, float(params["unknown_airport"]), set())
               for _ in _SIDES]
    rows = []
    for i in range(n_rows):
        day = rng.randint(1, days)
        date = datetime.date(year, month, day)
        k = rng.choices(range(n_car), cum_weights=car_cum)[0]
        code = carrier_code(k)
        row = [str(year), str((month - 1) // 3 + 1), str(month), str(day),
               str(date.isoweekday()), date.isoformat(), code,
               str(19000 + k * 37), code,
               f"N{rng.randint(100, 999)}{rng.choice(_A)}{rng.choice(_A)}",
               str(rng.randint(1, 6999))]
        for side, lost in zip(_SIDES, unknown):
            a = rng.choices(range(n_air), cum_weights=air_cum)[0]
            iata = airport_iata(a)
            if i in lost:
                iata = "Z" + rng.choice(_A) + rng.choice(_A)
            st = _STATES[a % len(_STATES)]
            row += [str(10000 + a * 7), str(1000000 + a * 700 + 2),
                    str(30000 + a * 7), iata,
                    f"{_CITY_1[a % len(_CITY_1)]}, {st[0]}",
                    st[0], st[1], st[2], st[3]]
        crs_dep = rng.randint(300, 1430)
        crs_el = rng.randint(40, 420)
        crs_arr = crs_dep + crs_el
        dist = max(31, int(crs_el * 7.2) + rng.randint(-150, 150))
        is_c, is_d = i in cancelled, i in diverted
        if is_c:
            dep = [_hhmm(crs_dep), "", "", "", "", "", _blk(crs_dep), "", "",
                   "", ""]
            arr = [_hhmm(crs_arr), "", "", "", "", "", _blk(crs_arr)]
            tail = ["1.00", rng.choice("ABCD"), "0.00", _num(crs_el), "", "",
                    "1.00", _num(dist), str(min(11, dist // 250 + 1))]
            causes = [""] * 5
        else:
            dd = rng.randint(-12, 8) if rng.random() < 0.7 \
                else rng.randint(9, 180)
            ad = rng.randint(15, 240) if i in late else rng.randint(-35, 14)
            t_out, t_in = rng.randint(5, 45), rng.randint(2, 25)
            elapsed = max(t_out + t_in + 10, crs_el + ad - dd)
            dep = [_hhmm(crs_dep), _hhmm(crs_dep + dd), _num(dd),
                   _num(max(dd, 0)), _num(dd >= 15),
                   str(max(-2, min(12, dd // 15))), _blk(crs_dep),
                   _num(t_out), _hhmm(crs_dep + dd + t_out),
                   "" if is_d else _hhmm(crs_dep + dd + elapsed - t_in),
                   "" if is_d else _num(t_in)]
            if is_d:       # the arrival block of a diverted flight is empty
                arr = [_hhmm(crs_arr), "", "", "", "", "", _blk(crs_arr)]
                tail = ["0.00", "", "1.00", _num(crs_el), "", "", "1.00",
                        _num(dist), str(min(11, dist // 250 + 1))]
                causes = [""] * 5
            else:
                arr = [_hhmm(crs_arr), _hhmm(crs_dep + dd + elapsed),
                       _num(ad), _num(max(ad, 0)), _num(ad >= 15),
                       str(max(-2, min(12, ad // 15))), _blk(crs_arr)]
                tail = ["0.00", "", "0.00", _num(crs_el), _num(elapsed),
                        _num(elapsed - t_out - t_in), "1.00", _num(dist),
                        str(min(11, dist // 250 + 1))]
                if i in late:
                    cut = sorted(rng.randint(0, ad) for _ in range(4))
                    parts = [b - a for a, b in zip([0] + cut, cut + [ad])]
                    causes = [_num(p) for p in parts]
                else:
                    causes = [""] * 5
        row += dep + arr + tail + causes
        row += ["", "", ""]              # gate returns: one flight in 200
        if is_d:
            reached = rng.random() < 0.5
            a = rng.randrange(n_air)
            row += ["1", _num(reached),
                    _num(crs_el + rng.randint(60, 300)) if reached else "",
                    _num(rng.randint(60, 400)) if reached else "",
                    _num(rng.randint(0, 300)),
                    airport_iata(a), str(10000 + a * 7),
                    str(1000000 + a * 700 + 2), _hhmm(crs_arr + 30),
                    _num(rng.randint(10, 90)), _num(rng.randint(10, 90)),
                    _hhmm(crs_arr + 95) if reached else "",
                    row[9]] + [""] * 32
        else:
            row += ["0" if not is_c else ""] + [""] * 44
        row.append("")                   # the trailing comma's column
        rows.append(row)
    return rows
