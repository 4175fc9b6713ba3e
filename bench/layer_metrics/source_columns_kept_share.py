"""Ingest: the columns a file source read as a share of the columns its
file has, over the window's `ingest:read-csv` spans (`columns` /
`file_columns`), each file weighted by its `bytes`. 100 means no column
was pruned at the read; None on a program whose spans lack `file_columns`
(before projection crossed joins, no span said how wide the file was)."""


def read(run: dict):
    kept = whole = 0.0
    for s in run["window"]["spans"]:
        a = s.get("args") or {}
        if s["name"] == "ingest:read-csv" and a.get("file_columns"):
            w = float(a.get("bytes") or 0) or 1.0
            kept += w * float(a.get("columns") or 0) / a["file_columns"]
            whole += w
    return 100.0 * kept / whole if whole else None
