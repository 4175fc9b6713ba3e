"""Hello tuplex_tpu: dual-mode in one line (reference:
examples/00_HelloTuplex.ipynb).

The None row raises TypeError inside the compiled fast path, falls back to
the interpreter tier, and is dropped (no resolver) — exactly CPython
semantics, counted in exception_counts().
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tuplex_tpu as tuplex

c = tuplex.Context()
ds = c.parallelize([1, 2, None, 4]).map(lambda x: (x, x * x))
print(ds.collect())            # [(1, 1), (2, 4), (4, 16)]
print(ds.exception_counts())   # {'TypeError': 1}
