"""Serverless fan-out: stages shipped to detached worker processes.

The reference's AWS Lambda backend serializes each stage (LLVM bitcode +
S3 URIs) and fans it out over Lambda invocations. Here the same
architecture runs over worker PROCESSES: the stage travels as a spec
(UDF sources + captured globals + schemas), multi-file sources split by
file per task, memory inputs stage native-format parts through a scratch
dir, and failed tasks retry then degrade to in-process execution.

Run: python examples/05_serverless.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import tuplex_tpu

tmp = tempfile.mkdtemp()
for f in range(4):
    with open(os.path.join(tmp, f"events-{f}.csv"), "w") as fp:
        fp.write("user,amount\n")
        for i in range(5000):
            fp.write(f"u{(f * 5000 + i) % 97},{(i % 400) - 20}\n")

c = tuplex_tpu.Context({
    "tuplex.backend": "lambda",              # or "serverless"
    "tuplex.aws.maxConcurrency": 4,          # concurrent workers
    "tuplex.aws.retryCount": 2,              # re-invocations before degrade
    "tuplex.aws.scratchDir": os.path.join(tmp, "scratch"),
})

# each worker reads its own file subset, runs the full dual-mode ladder
# (compiled fast path + general tier + interpreter resolve), and writes
# native-format parts the driver merges in order
top = (c.csv(os.path.join(tmp, "events-*.csv"))
       .filter(lambda x: x["amount"] > 0)
       .map(lambda x: {"user": x["user"], "amount": x["amount"]})
       .aggregateByKey(lambda a, b: a + b,
                       lambda a, x: a + x["amount"], 0, ["user"])
       .collect())

top.sort(key=lambda kv: -kv[1])
print("top spenders:", top[:5])
print("tasks failed/retried:", len(c.backend.failure_log))
