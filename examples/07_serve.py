"""Job service: many tenants' pipelines sharing one warm device.

A `Context` action is one-shot; `Context.submit()` hands the pipeline to
the long-lived job service instead (tuplex_tpu/serve/): bounded
admission with backpressure, deficit-weighted round-robin of STAGE
dispatches across tenants (no job monopolizes the chip), a shared
content-addressed compile plane (isomorphic jobs cost ~1 compile set),
and per-job memory budgets that spill instead of OOM-ing the process.
Each handle carries its tenant's own metrics, counter family and span
stream.

Run: python examples/07_serve.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import tuplex_tpu

tmp = tempfile.mkdtemp()
for tenant in ("alice", "bob"):
    with open(os.path.join(tmp, f"{tenant}.csv"), "w") as fp:
        fp.write("user,amount\n")
        for i in range(5000):
            fp.write(f"u{i % 97},{(i % 400) - 20}\n")

c = tuplex_tpu.Context({
    "tuplex.serve.queueDepth": 8,        # admission bound (backpressure)
    "tuplex.serve.jobMemory": "64MB",    # default per-job budget
    "tuplex.serve.tenantWeights": "alice:2,bob:1",
})

# two tenants submit concurrently; the scheduler interleaves their stage
# dispatches on the warm device instead of running them serially
handles = []
for tenant in ("alice", "bob"):
    ds = (c.csv(os.path.join(tmp, f"{tenant}.csv"))
          .filter(lambda x: x["amount"] > 0)
          .map(lambda x: (x["user"], x["amount"] * 100)))
    handles.append(c.submit(ds, name=f"{tenant}-etl", tenant=tenant,
                            memory_budget="32MB"))

for h in handles:
    rows = h.result(timeout=600)        # blocks until THIS job finishes
    print(f"{h.tenant}: {len(rows)} rows in {h.stats['turns']} turn(s), "
          f"resident {h.stats['resident_bytes']} B "
          f"of {h.stats['budget_bytes']} B budget")
    print(f"  counters: {h.counters()}")

c.close()
