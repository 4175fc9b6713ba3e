"""Agg/join: the columns of a join's output row, the mean of `columns_out`
over the window's `join:execute` spans. Every column of that row is
gathered once a match; a column that nothing downstream reads is work for
nothing. None on a program whose spans lack the attribute."""


def read(run: dict):
    cols = [(s.get("args") or {}).get("columns_out")
            for s in run["window"]["spans"] if s["name"] == "join:execute"]
    cols = [c for c in cols if c is not None]
    return sum(cols) / len(cols) if cols else None
