"""Collect: seconds of the window's `collect:box-partition` spans, one a
partition boxed into Python rows on the job's thread (its touch, the
decode and the splice of its `fallback` rows), as a share of the window's
job seconds. The span keeps its name wherever boxing runs. None on a
program without it."""

from harness import arith, reading


def read(run: dict):
    w = run["window"]
    return arith.share_pct(
        reading.span_seconds(w["spans"], ("collect:box-partition",)),
        reading.job_seconds(w))
