"""Stage exec: the stage records' `fast_path_s` (host clock around
parse, staging, dispatch and unpack of the compiled path) as a share of the
window's job seconds."""

from harness import arith, reading


def read(run: dict):
    w = run["window"]
    return arith.share_pct(reading.stage_sum(w["stages"], "fast_path_s"),
                           reading.job_seconds(w))
