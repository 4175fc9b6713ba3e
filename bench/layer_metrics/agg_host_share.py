"""Agg/join: the host's part of an aggregate, `agg:factorize-keys` (key
signatures and their unique codes, numpy) + `agg:host-merge` (the Python
merge of per-key partials, rows folded by the interpreter, the output
partition), as a share of the window's job seconds."""

from harness import arith, reading


def read(run: dict):
    w = run["window"]
    return arith.share_pct(
        reading.span_seconds(w["spans"],
                             ("agg:factorize-keys", "agg:host-merge")),
        reading.job_seconds(w))
