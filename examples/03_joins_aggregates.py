"""Broadcast hash join + aggregateByKey on device (reference:
test/core/JoinTest.cc, AggregateTest.cc).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tuplex_tpu as tuplex

c = tuplex.Context()
orders = c.parallelize(
    [(1, "apple", 3), (2, "pear", 1), (1, "plum", 9), (3, "apple", 2)],
    columns=["user", "item", "qty"])
users = c.parallelize(
    [(1, "ada"), (2, "grace"), (4, "edsger")], columns=["id", "name"])

joined = orders.join(users, "user", "id")
print(joined.collect())

totals = (orders
          .aggregateByKey(lambda a, b: a + b,
                          lambda a, x: a + x["qty"],
                          0, ["user"]))
print(totals.collect())
