"""Dirty-data cleaning over CSV: speculation + resolvers (reference:
examples/02_Working_with_files.ipynb, benchmarks/zillow).

Generates a small dirty file, then cleans it: the price column speculates
to i64; dirty cells ('N/A') violate the normal case and re-run on the
COMPILED general-case tier (price decoded as its raw string), which
reproduces the exact ValueError vectorized; the user's resolver then fires
on the interpreter tier and the resolved rows merge back in order.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tempfile

import tuplex_tpu as tuplex

path = os.path.join(tempfile.mkdtemp(), "sales.csv")
with open(path, "w") as f:
    f.write("city,price\n")
    for i in range(1000):
        price = "N/A" if i % 97 == 0 else str(100_000 + i)
        f.write(f"city{i % 7},{price}\n")

c = tuplex.Context()
ds = (c.csv(path)
      .withColumn("price_eur", lambda x: int(x["price"]) * 9 // 10)
      .resolve(ValueError, lambda x: -1)
      .filter(lambda x: x["price_eur"] != 0))
rows = ds.collect()
print(f"{len(rows)} clean rows; exceptions: {ds.exception_counts()}")
ds.explain()   # prints the physical plan
