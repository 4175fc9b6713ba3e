"""Synthetic Zillow listings: `zillow-z1-host4`'s own copy of the generator.

The code below the docstring is `bench/configs/zillow-z1/generate.py`'s,
line for line (`bench/tests/test_zillow_host4.py` holds the two together).
One call makes one chunk of one table from its own `random.Random`, so a
chunk is the same whatever process makes it and whatever else runs. The
share of deviant rows comes from the cell's parameters (`dirty_facts`,
`dirty_postal`), never from code.
"""

from __future__ import annotations

COLUMNS = {
    "listings": ["title", "address", "city", "state", "postal_code", "price",
                 "facts and features", "real estate provider", "url",
                 "sales_date"],
}

_CITIES = ["boston", "CAMBRIDGE", "Somerville", "newton", "BROOKLINE",
           "quincy", "medford", "arlington"]
_STATES = ["MA", "NY", "CA", "WA"]
_TITLES_SALE = ["House For Sale", "Colonial house for sale",
                "New construction house - for sale!", "Big house for sale"]
_TITLES_RENT = ["Condo for rent", "Apartment For Rent", "Studio for rent"]
_TITLES_SOLD = ["House recently sold", "Sold: lovely house"]
_PROVIDERS = ["RE/MAX", "Zillow", "Coldwell Banker", "agent"]
_BROKEN_FACTS = ["studio , no data", "-- , contact agent", ""]
_BROKEN_POSTAL = ["N/A", "0210A", ""]


def gen_chunk(table: str, rng, n_rows: int, first_row: int,
              params: dict) -> list:
    """`n_rows` rows of `table` as lists of strings, in COLUMNS order."""
    if table != "listings":
        raise ValueError(f"zillow-z1 has no table {table!r}")
    p_facts = float(params["dirty_facts"])
    p_postal = float(params["dirty_postal"])
    rows = []
    for _ in range(n_rows):
        kind = rng.random()
        bd = rng.randint(1, 12)
        ba = rng.randint(1, 5)
        sqft = rng.randint(400, 9000)
        dirty = rng.random()
        if kind < 0.55:
            title = rng.choice(_TITLES_SALE)
            price = f"${rng.randint(100, 3000) * 1000:,}"
        elif kind < 0.8:
            title = rng.choice(_TITLES_RENT)
            price = f"${rng.randint(800, 9000):,}/mo"
        else:
            title = rng.choice(_TITLES_SOLD)
            price = "--"
        facts = f"{bd} bds , {ba} ba , {sqft:,} sqft"
        if kind >= 0.8:
            facts += f" , Price/sqft: ${rng.randint(100, 900)} , more"
        if dirty < p_facts:
            facts = rng.choice(_BROKEN_FACTS)
        postal = f"{rng.randint(1000, 99999):05d}"
        if p_facts <= dirty < p_facts + p_postal:
            postal = rng.choice(_BROKEN_POSTAL)
        rows.append([
            title,
            f"{rng.randint(1, 999)} Main St",
            rng.choice(_CITIES),
            rng.choice(_STATES),
            postal,
            price,
            facts,
            rng.choice(_PROVIDERS),
            f"https://example.com/homes/{rng.randint(10**6, 10**7)}",
            f"202{rng.randint(0, 5)}-0{rng.randint(1, 9)}"
            f"-1{rng.randint(0, 9)}",
        ])
    return rows
