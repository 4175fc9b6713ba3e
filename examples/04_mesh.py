"""Row-sharded execution over a device mesh (SURVEY §2.10: distributed DP
via jax.sharding; run with XLA_FLAGS=--xla_force_host_platform_device_count=8
to simulate 8 devices on CPU).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tuplex_tpu as tuplex

c = tuplex.Context({"tuplex.backend": "multihost"})
ds = (c.parallelize(list(range(100_000)))
      .map(lambda x: x * x)
      .filter(lambda x: x % 7 == 0))
print(len(ds.collect()), "rows through the mesh backend")
