"""Collect: what one cell of the answer costs to box, in nanoseconds: the
seconds of the window's `collect:box-partition` spans over the sum of
`rows` x `columns` (top-level output columns) they carry. None on a
program without the span, or where no cell was boxed."""


def read(run: dict):
    spans = [s for s in run["window"]["spans"]
             if s["name"] == "collect:box-partition"]
    cells = sum((s.get("args") or {}).get("rows", 0)
                * (s.get("args") or {}).get("columns", 0) for s in spans)
    if not cells:
        return None
    return 1e3 * sum(s["dur"] for s in spans) / cells   # us -> ns
