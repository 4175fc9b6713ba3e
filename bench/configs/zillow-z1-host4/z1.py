"""Zillow Z1 for `zillow-z1-host4`: the pipeline (the workload asked of the
program), its plain CPython reference and the comparison that decides
`correct`. The configuration's own copy: the code below the docstring is
`bench/configs/zillow-z1/z1.py`'s, line for line
(`bench/tests/test_zillow_host4.py` holds the two together).

The UDFs are the published cleaning logic of upstream
`benchmarks/zillow/Z1/runtuplex.py`; the reference below runs the same
functions row by row in CPython and imports nothing of the program.
"""

from __future__ import annotations

FACT = "listings"
SIDE = ()
OUT_COLUMNS = ["url", "zipcode", "address", "city", "state", "bedrooms",
               "bathrooms", "sqft", "offer", "type", "price"]


def extractBd(x):
    val = x["facts and features"]
    max_idx = val.find(" bd")
    if max_idx < 0:
        max_idx = len(val)
    s = val[:max_idx]
    split_idx = s.rfind(",")
    if split_idx < 0:
        split_idx = 0
    else:
        split_idx += 2
    r = s[split_idx:]
    return int(r)


def extractBa(x):
    val = x["facts and features"]
    max_idx = val.find(" ba")
    if max_idx < 0:
        max_idx = len(val)
    s = val[:max_idx]
    split_idx = s.rfind(",")
    if split_idx < 0:
        split_idx = 0
    else:
        split_idx += 2
    r = s[split_idx:]
    return int(r)


def extractSqft(x):
    val = x["facts and features"]
    max_idx = val.find(" sqft")
    if max_idx < 0:
        max_idx = len(val)
    s = val[:max_idx]
    split_idx = s.rfind("ba ,")
    if split_idx < 0:
        split_idx = 0
    else:
        split_idx += 5
    r = s[split_idx:]
    r = r.replace(",", "")
    return int(r)


def extractOffer(x):
    offer = x["title"].lower()
    if "sale" in offer:
        return "sale"
    if "rent" in offer:
        return "rent"
    if "sold" in offer:
        return "sold"
    if "foreclose" in offer:
        return "foreclosed"
    return offer


def extractType(x):
    t = x["title"].lower()
    type_ = "unknown"
    if "condo" in t or "apartment" in t:
        type_ = "condo"
    if "house" in t:
        type_ = "house"
    return type_


def extractPrice(x):
    price = x["price"]
    p = 0
    if x["offer"] == "sold":
        val = x["facts and features"]
        s = val[val.find("Price/sqft:") + len("Price/sqft:") + 1:]
        r = s[s.find("$") + 1: s.find(", ") - 1]
        price_per_sqft = int(r)
        p = price_per_sqft * x["sqft"]
    elif x["offer"] == "rent":
        max_idx = price.rfind("/")
        p = int(price[1:max_idx].replace(",", ""))
    else:
        p = int(price[1:].replace(",", ""))
    return p


def build(ctx, paths: dict):
    """The Z1 chain over `ctx.csv(listings)`, not yet collected."""
    return (ctx.csv(paths["listings"])
            .withColumn("bedrooms", extractBd)
            .filter(lambda x: x["bedrooms"] < 10)
            .withColumn("type", extractType)
            .filter(lambda x: x["type"] == "house")
            .withColumn("zipcode", lambda x: "%05d" % int(x["postal_code"]))
            .mapColumn("city", lambda x: x[0].upper() + x[1:].lower())
            .withColumn("bathrooms", extractBa)
            .withColumn("sqft", extractSqft)
            .withColumn("offer", extractOffer)
            .withColumn("price", extractPrice)
            .filter(lambda x: 100000 < x["price"] <= 2e7)
            .selectColumns(OUT_COLUMNS))


def reference_partial(columns: list, rows: list, side: dict,
                      control: bool = False) -> list:
    """The Z1 chain in plain CPython over one chunk; a row whose UDF raises
    is dropped, as the program drops a row no tier can resolve."""
    out = []
    for rec in rows:
        try:
            x = dict(zip(columns, rec))
            x["bedrooms"] = extractBd(x)
            if not x["bedrooms"] < 10:
                continue
            x["type"] = extractType(x)
            if x["type"] != "house":
                continue
            x["zipcode"] = "%05d" % int(x["postal_code"])
            c = x["city"]
            x["city"] = c[0].upper() + c[1:].lower()
            x["bathrooms"] = extractBa(x)
            x["sqft"] = extractSqft(x)
            x["offer"] = extractOffer(x)
            x["price"] = extractPrice(x)
            if not 100000 < x["price"] <= 2e7:
                continue
            out.append(tuple(x[c] for c in OUT_COLUMNS))
        except Exception:       # the UDF's own error: the row is dropped
            continue
    return out


def reference_merge(partials: list, control: bool = False) -> list:
    """Chunks in input order. The control breaks the configuration's order
    guarantee the way a merge by completion would: the last chunk first."""
    if control and len(partials) > 1:
        partials = [partials[-1]] + list(partials[:-1])
    return [row for part in partials for row in part]


def compare(got: list, want: list, limits: dict) -> list:
    """(name, value, limit): exact rows in order, so both limits are 0."""
    differ = sum(1 for g, w in zip(got, want) if tuple(g) != w)
    return [("rows_missing_or_extra", abs(len(got) - len(want)), 0),
            ("rows_differ", differ, 0)]


def answer_bytes(answer: list) -> int:
    """Bytes of the answer as a program must hand it over: string bytes
    as UTF-8 and 8 bytes for each integer."""
    n = 0
    for row in answer:
        for v in row:
            n += len(v.encode()) if isinstance(v, str) else 8
    return n
