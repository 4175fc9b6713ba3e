"""Full-width TPC-H `lineitem` (16 columns) and `part` (9 columns).

Value domains and correlations follow the TPC-H specification, clause
4.2.3: prices from the part key, ship, commit and receipt dates from the
order date, the return flag and line status from those dates and
CURRENTDATE. What departs from dbgen is listed under `assumed` in
`tpch-sf033.json`. One call makes one chunk from its own numpy generator,
so a chunk is the same whatever process makes it.
"""

from __future__ import annotations

import numpy as np

COLUMNS = {
    "lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_linenumber",
                 "l_quantity", "l_extendedprice", "l_discount", "l_tax",
                 "l_returnflag", "l_linestatus", "l_shipdate",
                 "l_commitdate", "l_receiptdate", "l_shipinstruct",
                 "l_shipmode", "l_comment"],
    "part": ["p_partkey", "p_name", "p_mfgr", "p_brand", "p_type", "p_size",
             "p_container", "p_retailprice", "p_comment"],
}

START_DATE = np.datetime64("1992-01-01")
END_DATE = np.datetime64("1998-12-31")
CURRENT_DATE = np.datetime64("1995-06-17")

INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
CONTAINER_1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAINER_2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
TYPE_1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
COLOURS = ["almond", "antique", "aquamarine", "azure", "beige", "bisque",
           "black", "blanched", "blue", "blush", "brown", "burlywood",
           "burnished", "chartreuse", "chiffon", "chocolate", "coral",
           "cornflower", "cornsilk", "cream", "cyan", "dark", "deep", "dim",
           "dodger", "drab", "firebrick", "floral", "forest", "frosted",
           "gainsboro", "ghost", "goldenrod", "green", "grey", "honeydew",
           "hot", "indian", "ivory", "khaki", "lace", "lavender", "lawn",
           "lemon", "light", "lime", "linen", "magenta", "maroon", "medium"]
WORDS = ["furiously", "sly", "careful", "blithe", "quick", "fluffy", "slow",
         "quiet", "ruthless", "thin", "close", "dogged", "daring", "brave",
         "stealthy", "permanent", "enticing", "idle", "busy", "regular",
         "final", "ironic", "even", "bold", "silent", "packages",
         "requests", "accounts", "deposits", "foxes", "ideas", "theodolites",
         "pinto", "beans", "instructions", "dependencies", "excuses",
         "platelets", "asymptotes", "courts", "dolphins", "multipliers",
         "sleep", "wake", "are", "cajole", "haggle", "nag", "use", "boost",
         "affix", "detect", "integrate", "maintain", "nod", "was", "lose",
         "about", "above", "according", "to", "across", "after", "against",
         "along", "among", "around", "at", "before", "behind", "beside"]


def retail_cents(partkey):
    """p_retailprice in cents (clause 4.2.3):
    90000 + ((p_partkey / 10) modulo 20001) + 100 * (p_partkey modulo 1000)."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def _money(cents) -> list:
    return [f"{c // 100}.{c % 100:02d}" for c in cents.tolist()]


def _text(rng, n: int, lo: int, hi: int) -> list:
    """`n` strings of lo..hi characters cut from random words."""
    words = rng.integers(0, len(WORDS), size=(n, 8))
    lens = rng.integers(lo, hi + 1, size=n).tolist()
    out = []
    for w, k in zip(words.tolist(), lens):
        out.append(" ".join(WORDS[i] for i in w)[:k].rstrip())
    return out


def _pick(rng, choices: list, n: int) -> list:
    idx = rng.integers(0, len(choices), size=n)
    return [choices[i] for i in idx.tolist()]


def _dates(d) -> list:
    return np.datetime_as_string(d, unit="D").tolist()


def gen_lineitem(rng, n_rows: int, first_row: int, params: dict) -> list:
    n_part = int(params["_rows"]["part"])
    n_supp = max(4, n_part // 20)      # SF * 10,000 suppliers
    # orders of 1..7 lines each, the last cut where the chunk ends; the
    # order index is global, so order keys never collide between chunks
    per_order = rng.integers(1, 8, size=n_rows)
    ends = np.cumsum(per_order)
    n_orders = int(np.searchsorted(ends, n_rows) + 1)
    per_order = per_order[:n_orders]
    order_of = np.repeat(np.arange(n_orders), per_order)[:n_rows]
    starts = np.concatenate(([0], ends[:n_orders - 1]))
    linenumber = np.arange(n_rows) - starts[order_of] + 1
    g = first_row + order_of
    orderkey = (g // 8) * 32 + g % 8 + 1        # dbgen's sparse order keys
    span = int((END_DATE - np.timedelta64(151, "D") - START_DATE)
               / np.timedelta64(1, "D"))
    odate = START_DATE + rng.integers(0, span + 1, size=n_orders).astype(
        "timedelta64[D]")
    odate = odate[order_of]
    partkey = rng.integers(1, n_part + 1, size=n_rows)
    corner = rng.integers(0, 4, size=n_rows)
    suppkey = (partkey + corner * (n_supp // 4 + (partkey - 1) // n_supp)) \
        % n_supp + 1
    quantity = rng.integers(1, 51, size=n_rows)
    extended = quantity * retail_cents(partkey)
    discount = rng.integers(0, 11, size=n_rows)
    tax = rng.integers(0, 9, size=n_rows)
    ship = odate + rng.integers(1, 122, size=n_rows).astype("timedelta64[D]")
    commit = odate + rng.integers(30, 91, size=n_rows).astype(
        "timedelta64[D]")
    receipt = ship + rng.integers(1, 31, size=n_rows).astype(
        "timedelta64[D]")
    ra = np.where(rng.integers(0, 2, size=n_rows) == 0, "R", "A")
    flag = np.where(receipt <= CURRENT_DATE, ra, "N").tolist()
    status = np.where(ship > CURRENT_DATE, "O", "F").tolist()
    cols = [orderkey.tolist(), partkey.tolist(), suppkey.tolist(),
            linenumber.tolist(), quantity.tolist(), _money(extended),
            [f"0.{d:02d}" for d in discount.tolist()],
            [f"0.{t:02d}" for t in tax.tolist()],
            flag, status, _dates(ship), _dates(commit), _dates(receipt),
            _pick(rng, INSTRUCTIONS, n_rows), _pick(rng, MODES, n_rows),
            _text(rng, n_rows, 10, 43)]
    return [list(map(str, r)) for r in zip(*cols)]


def gen_part(rng, n_rows: int, first_row: int, params: dict) -> list:
    key = np.arange(first_row + 1, first_row + n_rows + 1)
    m = rng.integers(1, 6, size=n_rows)
    n = rng.integers(1, 6, size=n_rows)
    names = []
    for _ in range(n_rows):
        names.append(" ".join(COLOURS[i] for i in
                              rng.choice(len(COLOURS), 5, replace=False)))
    t1, t2, t3 = (_pick(rng, t, n_rows) for t in (TYPE_1, TYPE_2, TYPE_3))
    c1, c2 = _pick(rng, CONTAINER_1, n_rows), _pick(rng, CONTAINER_2, n_rows)
    cols = [key.tolist(), names,
            [f"Manufacturer#{a}" for a in m.tolist()],
            [f"Brand#{a}{b}" for a, b in zip(m.tolist(), n.tolist())],
            [f"{a} {b} {c}" for a, b, c in zip(t1, t2, t3)],
            rng.integers(1, 51, size=n_rows).tolist(),
            [f"{a} {b}" for a, b in zip(c1, c2)],
            _money(retail_cents(key)), _text(rng, n_rows, 5, 22)]
    return [list(map(str, r)) for r in zip(*cols)]


def gen_chunk(table: str, rng, n_rows: int, first_row: int,
              params: dict) -> list:
    """`n_rows` rows of `table` as lists of strings, in COLUMNS order.
    `rng` is the chunk's `random.Random`; numpy draws from a generator
    seeded by it, so the chunk depends on nothing else."""
    nrng = np.random.default_rng(rng.getrandbits(64))
    if table == "lineitem":
        return gen_lineitem(nrng, n_rows, first_row, params)
    if table == "part":
        return gen_part(nrng, n_rows, first_row, params)
    raise ValueError(f"tpch-sf033 has no table {table!r}")
