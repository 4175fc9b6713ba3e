"""TPC-H Q1, the pricing summary report (specification clause 2.4.1), with
DELTA = 90: the pipeline, its plain CPython reference and the comparison.

The averages of the query are quotients of the sums and the count below,
so the aggregate carries the sums: quantity, base price, discounted price,
charge, discount, and the count.
"""

from __future__ import annotations

import math

FACT = "lineitem"
SIDE = ()
CUTOFF = "1998-09-02"           # date '1998-12-01' - interval '90' day


def build(ctx, paths: dict):
    return (ctx.csv(paths["lineitem"])
            .filter(lambda x: x["l_shipdate"] <= "1998-09-02")
            .aggregateByKey(
                lambda a, b: (a[0] + b[0], a[1] + b[1], a[2] + b[2],
                              a[3] + b[3], a[4] + b[4], a[5] + b[5]),
                lambda a, x: (a[0] + x["l_quantity"],
                              a[1] + x["l_extendedprice"],
                              a[2] + x["l_extendedprice"] *
                              (1 - x["l_discount"]),
                              a[3] + x["l_extendedprice"] *
                              (1 - x["l_discount"]) * (1 + x["l_tax"]),
                              a[4] + x["l_discount"],
                              a[5] + 1),
                (0, 0.0, 0.0, 0.0, 0.0, 0),
                ["l_returnflag", "l_linestatus"]))


def reference_partial(columns: list, rows: list, side: dict,
                      control: bool = False) -> dict:
    """Per group: the exact integer sums and, for each float sum, the list
    of this chunk's terms folded by `math.fsum` (correctly rounded). The
    control folds the same terms in float32, the nearest precision below
    the float64 that the configuration states."""
    i = {c: columns.index(c) for c in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_returnflag", "l_linestatus", "l_shipdate")}
    terms: dict = {}
    for r in rows:
        if r[i["l_shipdate"]] <= CUTOFF:
            price = float(r[i["l_extendedprice"]])
            disc = float(r[i["l_discount"]])
            tax = float(r[i["l_tax"]])
            t = terms.setdefault(
                (r[i["l_returnflag"]], r[i["l_linestatus"]]),
                [0, [], [], [], [], 0])
            t[0] += int(r[i["l_quantity"]])
            t[1].append(price)
            t[2].append(price * (1 - disc))
            t[3].append(price * (1 - disc) * (1 + tax))
            t[4].append(disc)
            t[5] += 1
    fold = _fold32 if control else math.fsum
    return {k: (t[0], fold(t[1]), fold(t[2]), fold(t[3]), fold(t[4]), t[5])
            for k, t in terms.items()}


def _fold32(values: list) -> float:
    import numpy as np

    acc = np.float32(0.0)
    for v in np.asarray(values, dtype=np.float32):
        acc = np.float32(acc + v)
    return float(acc)


def reference_merge(partials: list, control: bool = False) -> dict:
    keys = sorted({k for p in partials for k in p})
    out = {}
    for k in keys:
        parts = [p[k] for p in partials if k in p]
        fold = (lambda v: _fold32(v)) if control else math.fsum
        out[k] = (sum(p[0] for p in parts),
                  fold([p[1] for p in parts]), fold([p[2] for p in parts]),
                  fold([p[3] for p in parts]), fold([p[4] for p in parts]),
                  sum(p[5] for p in parts))
    return out


def _as_groups(got) -> dict:
    if isinstance(got, dict):
        return got
    return {(r[0], r[1]): tuple(r[2:]) for r in got}


def compare(got, want: dict, limits: dict) -> list:
    """Groups and the integer columns exact; each float sum by its gap
    relative to the reference's sum, the widest over groups and sums."""
    got = _as_groups(got)
    groups = len(set(got) ^ set(want))
    ints = 0
    rel = 0.0
    for k in set(got) & set(want):
        g, w = got[k], want[k]
        ints += int(g[0] != w[0]) + int(g[5] != w[5])
        for j in (1, 2, 3, 4):
            rel = max(rel, abs(g[j] - w[j]) / max(abs(w[j]), 1e-300))
    return [("groups_differ", groups, 0), ("int_sums_differ", ints, 0),
            ("sum_rel_gap", rel, limits["sum_rel_gap"])]


def answer_bytes(answer: dict) -> int:
    return sum(len(k[0]) + len(k[1]) + 8 * len(v) for k, v in answer.items())
