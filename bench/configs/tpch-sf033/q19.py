"""TPC-H Q19, the discounted revenue query (specification clause 2.4.19):
`lineitem` joined with `part` under a three-branch predicate, then one
sum. The pipeline, its plain CPython reference and the comparison.

The specification's text asks for l_shipmode in ('AIR', 'AIR REG'); the
mode list has 'REG AIR', so only 'AIR' ever matches, here as in dbgen data.
"""

from __future__ import annotations

import math

FACT = "lineitem"
SIDE = ("part",)

_SM = ("SM CASE", "SM BOX", "SM PACK", "SM PKG")
_MED = ("MED BAG", "MED BOX", "MED PKG", "MED PACK")
_LG = ("LG CASE", "LG BOX", "LG PACK", "LG PKG")


def _q19_pred(x) -> bool:
    return ((x["p_brand"] == "Brand#12"
             and x["p_container"] in ("SM CASE", "SM BOX", "SM PACK",
                                      "SM PKG")
             and 1 <= x["l_quantity"] <= 11 and 1 <= x["p_size"] <= 5)
            or (x["p_brand"] == "Brand#23"
                and x["p_container"] in ("MED BAG", "MED BOX", "MED PKG",
                                         "MED PACK")
                and 10 <= x["l_quantity"] <= 20 and 1 <= x["p_size"] <= 10)
            or (x["p_brand"] == "Brand#34"
                and x["p_container"] in ("LG CASE", "LG BOX", "LG PACK",
                                         "LG PKG")
                and 20 <= x["l_quantity"] <= 30
                and 1 <= x["p_size"] <= 15))


def build(ctx, paths: dict):
    part = ctx.csv(paths["part"])
    li = (ctx.csv(paths["lineitem"])
          .filter(lambda x: x["l_shipinstruct"] == "DELIVER IN PERSON")
          .filter(lambda x: x["l_shipmode"] == "AIR" or
                  x["l_shipmode"] == "AIR REG"))
    joined = li.join(part, "l_partkey", "p_partkey")
    return (joined
            .filter(_q19_pred)
            .aggregate(lambda a, b: a + b,
                       lambda a, x: a + x["l_extendedprice"] *
                       (1 - x["l_discount"]), 0.0))


def reference_partial(columns: list, rows: list, side: dict,
                      control: bool = False) -> float:
    pc = side["part"]["columns"]
    pi = {c: pc.index(c) for c in ("p_partkey", "p_brand", "p_size",
                                   "p_container")}
    parts = side["part"].get("_by_key")
    if parts is None:
        parts = side["part"]["_by_key"] = {
            int(r[pi["p_partkey"]]): (r[pi["p_brand"]], int(r[pi["p_size"]]),
                                      r[pi["p_container"]])
            for r in side["part"]["rows"]}
    i = {c: columns.index(c) for c in (
        "l_partkey", "l_quantity", "l_extendedprice", "l_discount",
        "l_shipinstruct", "l_shipmode")}
    terms = []
    for r in rows:
        if r[i["l_shipinstruct"]] != "DELIVER IN PERSON" or \
                r[i["l_shipmode"]] not in ("AIR", "AIR REG"):
            continue
        p = parts.get(int(r[i["l_partkey"]]))
        if p is None:
            continue
        brand, size, container = p
        qty = int(r[i["l_quantity"]])
        if ((brand == "Brand#12" and container in _SM
             and 1 <= qty <= 11 and 1 <= size <= 5)
                or (brand == "Brand#23" and container in _MED
                    and 10 <= qty <= 20 and 1 <= size <= 10)
                or (brand == "Brand#34" and container in _LG
                    and 20 <= qty <= 30 and 1 <= size <= 15)):
            terms.append(float(r[i["l_extendedprice"]]) *
                         (1 - float(r[i["l_discount"]])))
    return _fold32(terms) if control else math.fsum(terms)


def _fold32(values: list) -> float:
    import numpy as np

    acc = np.float32(0.0)
    for v in np.asarray(values, dtype=np.float32):
        acc = np.float32(acc + v)
    return float(acc)


def reference_merge(partials: list, control: bool = False) -> float:
    return _fold32(partials) if control else math.fsum(partials)


def compare(got, want: float, limits: dict) -> list:
    got = list(got) if isinstance(got, (list, tuple)) else [got]
    g = got[0] if got else float("nan")
    return [("answers_missing_or_extra", abs(len(got) - 1), 0),
            ("sum_rel_gap", abs(g - want) / max(abs(want), 1e-300),
             limits["sum_rel_gap"])]


def answer_bytes(answer: float) -> int:
    return 8
