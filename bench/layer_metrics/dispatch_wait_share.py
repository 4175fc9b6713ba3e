"""Stage exec: seconds the job thread spent in `dispatch:device-wait`
spans (devprof's poll for each dispatch's device work, which keeps the
next partition from overlapping it), as a share of the window's job
seconds."""

from harness import arith, reading


def read(run: dict):
    w = run["window"]
    return arith.share_pct(
        reading.span_seconds(w["spans"], ("dispatch:device-wait",)),
        reading.job_seconds(w))
