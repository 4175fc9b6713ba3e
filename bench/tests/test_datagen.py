"""Generation: the same seed gives the same bytes whatever the number of
helpers, and the full-width TPC-H tables keep the specification's shapes."""

import csv
import datetime
import hashlib
import os

import pytest

from harness import datagen, spec

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _digest(path: str) -> str:
    with open(path, "rb") as fp:
        return hashlib.sha256(fp.read()).hexdigest()


def _cell(name: str, rows: int) -> spec.Cell:
    cell = spec.Cell(name)
    cell.scale_rows(rows)
    return cell


def test_chunk_plan_covers_the_rows():
    assert datagen.chunk_plan(10, 4) == [(0, 0, 4), (1, 4, 4), (2, 8, 2)]
    assert datagen.chunk_plan(8, 4) == [(0, 0, 4), (1, 4, 4)]


@pytest.mark.parametrize("name", ["zillow-z1.dirty6", "tpch-sf033.q19"])
def test_same_seed_same_bytes_whatever_the_helpers(name, tmp_path,
                                                   inline_pool):
    cell = _cell(name, 4000)
    seed = 4_000_000_123                  # more than 32 signed bits hold
    a = datagen.generate(inline_pool, cell, seed, str(tmp_path / "a"))
    digests = {t: _digest(p) for t, p in a.paths.items()}
    for workers in (1, 3):
        pool = datagen.pool(BENCH_DIR, workers)
        try:
            b = datagen.generate(pool, cell, seed,
                                 str(tmp_path / f"w{workers}"))
        finally:
            pool.shutdown(wait=True)
        assert {t: _digest(p) for t, p in b.paths.items()} == digests
        assert b.rows == a.rows
    other = datagen.generate(inline_pool, cell, seed + 1,
                             str(tmp_path / "other"))
    assert all(_digest(p) != digests[t] for t, p in other.paths.items())


def test_zillow_deviant_share_is_the_cells_parameter(tmp_path, inline_pool):
    cell = _cell("zillow-z1.dirty6", 20000)
    inp = datagen.generate(inline_pool, cell, 11, str(tmp_path / "z"))
    with open(inp.paths["listings"], newline="") as fp:
        rows = list(csv.DictReader(fp))
    assert len(rows) == 20000 and len(rows[0]) == 10
    broken_facts = sum(1 for r in rows
                       if " bds " not in r["facts and features"])
    broken_postal = sum(1 for r in rows if not r["postal_code"].isdigit())
    assert 0.03 < broken_facts / len(rows) < 0.05
    assert 0.012 < broken_postal / len(rows) < 0.028
    width = os.path.getsize(inp.paths["listings"]) / len(rows)
    assert 140 < width < 156


def test_lineitem_is_full_width_with_the_specifications_domains(
        tmp_path, inline_pool):
    cell = _cell("tpch-sf033.q19", 20000)
    inp = datagen.generate(inline_pool, cell, 12, str(tmp_path / "t"))
    gen = cell.generator()
    with open(inp.paths["lineitem"], newline="") as fp:
        r = csv.reader(fp)
        header = next(r)
        rows = list(r)
    assert header == gen.COLUMNS["lineitem"] and len(header) == 16
    assert len(rows) == 20000
    n_part = cell.tables["part"]["rows"]
    day = datetime.date.fromisoformat
    current = datetime.date(1995, 6, 17)
    modes, instr = set(), set()
    for rec in rows:
        x = dict(zip(header, rec))
        pk, qty = int(x["l_partkey"]), int(x["l_quantity"])
        assert 1 <= pk <= n_part and 1 <= qty <= 50
        assert 1 <= int(x["l_linenumber"]) <= 7
        cents = round(float(x["l_extendedprice"]) * 100)
        assert cents == qty * gen.retail_cents(pk)
        assert 0.0 <= float(x["l_discount"]) <= 0.10
        assert 0.0 <= float(x["l_tax"]) <= 0.08
        ship, receipt = day(x["l_shipdate"]), day(x["l_receiptdate"])
        assert 1 <= (receipt - ship).days <= 30
        assert datetime.date(1992, 1, 2) <= ship <= datetime.date(1998, 12, 1)
        assert x["l_returnflag"] in ("R", "A") if receipt <= current \
            else x["l_returnflag"] == "N"
        assert x["l_linestatus"] == ("O" if ship > current else "F")
        assert 10 <= len(x["l_comment"]) <= 43 or x["l_comment"]
        modes.add(x["l_shipmode"])
        instr.add(x["l_shipinstruct"])
    assert modes == set(gen.MODES) and instr == set(gen.INSTRUCTIONS)
    keys = [int(rec[0]) for rec in rows]
    assert keys == sorted(keys)
    with open(inp.paths["part"], newline="") as fp:
        r = csv.reader(fp)
        pheader = next(r)
        parts = list(r)
    assert pheader == gen.COLUMNS["part"] and len(pheader) == 9
    assert [int(p[0]) for p in parts] == list(range(1, n_part + 1))
    assert all(p[3].startswith("Brand#") and 1 <= int(p[5]) <= 50
               for p in parts)
