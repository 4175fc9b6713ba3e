"""Stage exec: seconds of the window's `partition:merge` spans (a
partition's output built from the stage's outputs: device-resident, a
host merge, or a host merge that splices resolved rows; the span's
`path`) as a share of the window's job seconds."""

from harness import arith, reading


def read(run: dict):
    w = run["window"]
    return arith.share_pct(
        reading.span_seconds(w["spans"], ("partition:merge",)),
        reading.job_seconds(w))
