"""Mesh: rows of the largest input shard over rows of the smallest, from
the backend's `shard_layout` of its largest dispatch; 100% is even."""


def read(run: dict):
    lay = run["shard_layout"] or {}
    rows = [shape[0] for _, shape in lay.get("input", ()) if shape]
    if not rows or min(rows) <= 0:
        return None
    return 100.0 * max(rows) / min(rows)
