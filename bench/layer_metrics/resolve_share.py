"""Resolve: the stage records' `general_path_s` + `slow_path_s` (the
general tier and the interpreter) as a share of the window's job seconds."""

from harness import arith, reading


def read(run: dict):
    w = run["window"]
    g = reading.stage_sum(w["stages"], "general_path_s")
    s = reading.stage_sum(w["stages"], "slow_path_s")
    if g is None and s is None:
        return None
    return arith.share_pct((g or 0.0) + (s or 0.0), reading.job_seconds(w))
