"""Resolve: what a row costs the interpreter tier. The stage records'
`slow_path_s` (decoding the rows no compiled tier could finish, running
them through the stage's CPython closure chain, and the bookkeeping of
their exceptions) over `resolve_interpreter_rows`, in microseconds a row.
None where no row of the window went there."""

from harness import reading


def read(run: dict):
    st = run["window"]["stages"]
    rows = reading.stage_sum(st, "resolve_interpreter_rows")
    seconds = reading.stage_sum(st, "slow_path_s")
    if not rows or seconds is None:
        return None
    return 1e6 * seconds / rows
