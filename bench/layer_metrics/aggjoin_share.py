"""Agg/join: `agg:execute` + `join:execute` span seconds as a share of
the window's job seconds."""

from harness import arith, reading


def read(run: dict):
    w = run["window"]
    return arith.share_pct(
        reading.span_seconds(w["spans"], ("agg:execute", "join:execute")),
        reading.job_seconds(w))
