"""Job: the share of the window's job seconds that no span names. What is
named: the direct children of the window's `job` spans (`parent` is the
job's `id`) and the top-level spans other than `job` on the job's thread
(`ingest:sniff`, which runs in `ctx.csv` before the `job` span opens).
Spans of other threads (pool compiles, the prefetch producer) overlap the
job thread's time and are not subtracted from it."""

from harness import arith, reading


def named_seconds(spans: list):
    """Seconds named on the job threads of `spans`: direct children of the
    `job` spans and top-level spans beside them. None where the records
    carry no `id`/`parent` (a program without them) or hold no job."""
    jobs = [s for s in spans if s["name"] == "job" and s.get("id")]
    if not jobs:
        return None
    job_of = {s["id"]: s["tid"] for s in jobs}
    tids = set(job_of.values())
    named = 0.0
    for s in spans:
        if s["name"] == "job":
            continue
        parent = s.get("parent")
        if parent is None:
            if s["tid"] in tids and s.get("depth", 0) == 0:
                named += s["dur"]
        elif job_of.get(parent) == s["tid"]:
            named += s["dur"]
    return named / 1e6


def read(run: dict):
    w = run["window"]
    total = reading.job_seconds(w)
    named = named_seconds(w["spans"])
    if total is None or named is None:
        return None
    return arith.share_pct(max(total - named, 0.0), total)
