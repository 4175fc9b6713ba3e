"""Transfer, mesh backend: seconds of the window inside those of the
program's `d2h:leaf-fetch` spans that gather row-sharded outputs (the span
carries `shards`, the shards fetched in all, and `devices`), as a share of
the window's job seconds. None where no fetch says it was sharded: one
chip, or a program that does not count the shards."""

from harness import arith, reading


def read(run: dict):
    w = run["window"]
    durs = [s["dur"] for s in w["spans"] if s["name"] == "d2h:leaf-fetch"
            and (s.get("args") or {}).get("shards")]
    if not durs:
        return None
    return arith.share_pct(sum(durs) / 1e6, reading.job_seconds(w))
