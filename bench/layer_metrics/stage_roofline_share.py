"""Kernels: the least time the chip(s) need at the HBM peak for the bytes
one job must touch (its input files plus the reference answer's bytes: the
same work whatever implements it) over the device-busy seconds of the
traced job. Memory-bound by construction."""

from harness import arith


def read(run: dict):
    if run["trace"] is None or run["peaks"] is None:
        return None
    c = run["cell"]
    return arith.roofline_share_pct(
        c["input_bytes"] + c["answer_bytes"],
        run["peaks"]["hbm_bytes_per_s"], c["chips"], run["trace"]["busy_s"])
