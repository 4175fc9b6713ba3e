"""Ingest: what ingest still costs the thread the job waits on. Seconds of
the window's `ingest` spans (a source's read and plan, and each pull that
cuts a partition), `ingest:sniff` spans and `source:wait` spans (the stage
waiting for the prefetch thread's next partition) that lie on a job's
thread (the `tid` of a `job` span), as a share of the window's job seconds.
`ingest_share` counts every ingest second, whatever thread spent it; this
one leaves out what the prefetch thread did beside the chip."""

from harness import arith, reading

NAMES = ("ingest", "ingest:sniff", "source:wait")


def read(run: dict):
    w = run["window"]
    tids = {s["tid"] for s in w["spans"]
            if s["name"] == "job" and s.get("id")}
    if not tids:
        return None
    return arith.share_pct(
        reading.span_seconds([s for s in w["spans"] if s["tid"] in tids],
                             NAMES),
        reading.job_seconds(w))
