"""Transfer, mesh backend: seconds of the window inside the program's
`h2d:mesh-put` spans (the row-sharded placement of a dispatch's batch on
the mesh's devices, leaf by leaf) and its `mesh:pad-batch` spans (the copy
that pads a batch to a multiple of the mesh size), as a share of the
window's job seconds. None where the program opens no such span."""

from harness import arith, reading


def read(run: dict):
    w = run["window"]
    return arith.share_pct(
        reading.span_seconds(w["spans"], ("h2d:mesh-put", "mesh:pad-batch")),
        reading.job_seconds(w))
