"""Ingest: seconds of the window inside the program's `ingest` spans (the
wrapper around a source's load: CSV read, leaf building, harmonizing; not
its children a second time) and its `ingest:sniff` spans (the sample read
and type sniffing of `ctx.csv`), as a share of the window's job seconds."""

from harness import arith, reading


def read(run: dict):
    w = run["window"]
    return arith.share_pct(
        reading.span_seconds(w["spans"], ("ingest", "ingest:sniff")),
        reading.job_seconds(w))
