"""Resolve: rows retired by the interpreter tier as a share of the rows
the window's stages saw."""

from harness import arith, reading


def read(run: dict):
    st = run["window"]["stages"]
    return arith.share_pct(reading.stage_sum(st, "resolve_interpreter_rows"),
                           reading.stage_sum(st, "rows_seen"))
