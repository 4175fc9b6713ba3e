"""Agg/join: the rows of the window's by-key folds whose group codes were
matched on the device against the aggregate's key table (`agg:segment-fold`
spans with `path` "device-table"), as a share of the rows of all its by-key
folds (those, and "host-codes": keys factorized on the host). A span with
another `path` folded no row ("table-miss": the host factorizes and the
fold is launched again), and one without `path` is no by-key fold of this
kind (the scalar, scan and mesh folds; a program from before the key table).
None where the window has no such fold."""

from harness import arith

PATHS = ("device-table", "host-codes")


def read(run: dict):
    rows = {p: 0 for p in PATHS}
    seen = False
    for s in run["window"]["spans"]:
        args = s.get("args") or {}
        if s["name"] == "agg:segment-fold" and args.get("path") in PATHS:
            rows[args["path"]] += int(args.get("rows", 0))
            seen = True
    if not seen:
        return None
    return arith.share_pct(rows["device-table"], sum(rows.values()))
