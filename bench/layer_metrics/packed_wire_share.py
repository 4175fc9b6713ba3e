"""Transfer, one chip: seconds of the window inside the program's
`h2d:packed-upload` spans (a dispatch's batch packed into one host buffer
and placed on the device) and its `d2h:packed-fetch` spans (the stage's
outputs fetched as one buffer and unpacked, `d2h:varlen-unpack` inside
it), as a share of the window's job seconds. None where the program opens
no such span: the mesh backend, which stages leaf by leaf, or tracing
off."""

from harness import arith, reading


def read(run: dict):
    w = run["window"]
    return arith.share_pct(
        reading.span_seconds(w["spans"],
                             ("h2d:packed-upload", "d2h:packed-fetch")),
        reading.job_seconds(w))
