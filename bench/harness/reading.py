"""What the per-layer readers share: sums over the program's stage records
and span ring, as the harness hands them over in `run`.

`run` holds: `cell` (name, chips, rows and input_bytes of one job,
answer_bytes), `device`, `rehearsal`, `peaks`, `plan_ms`, `first_job` and
`window` (each with `seconds`, `stages`, `spans`, `cq`, `xfer`; the window
also `jobs`, `good`, `rows`), `trace` (the reduction of the traced job, or
None), `memory_peak_bytes`, `shard_layout`. A reader returns a number, or
None where it finds nothing to read.
"""

from __future__ import annotations


def span_seconds(spans: list, names: tuple):
    """Summed duration of the spans called one of `names`; None where
    there is none (tracing off, or the path never ran)."""
    durs = [s["dur"] for s in spans if s["name"] in names]
    return sum(durs) / 1e6 if durs else None


def stage_sum(stages: list, key: str):
    vals = [m[key] for m in stages if m.get(key) is not None]
    return float(sum(vals)) if vals else None


def job_seconds(window: dict):
    """Wall seconds of the window's jobs, the whole of what the stage
    records and spans of the window can add up to."""
    s = sum(j["seconds"] for j in window["jobs"])
    return s if s > 0 else None
