"""Collect: the share of the answer's rows boxed inside the stage's
dispatch window: `rows` of the window's `collect:box-partition` spans with
`in_window` 1 (the last stage's collect boxed the partition while the chip
ran the dispatches behind it) over `rows` of all of them, in %. None where
no span carries `in_window` (a program that boxes only after the plan), or
no row was boxed."""


def read(run: dict):
    spans = [s for s in run["window"]["spans"]
             if s["name"] == "collect:box-partition"
             and "in_window" in (s.get("args") or {})]
    rows = sum(s["args"].get("rows", 0) for s in spans)
    if not rows:
        return None
    inside = sum(s["args"].get("rows", 0) for s in spans
                 if s["args"]["in_window"])
    return 100.0 * inside / rows
