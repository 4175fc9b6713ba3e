"""Stage exec: the seconds of the window's `stage:execute` spans that no
child names, as a share of the window's job seconds. A stage's own seconds
are its span's less those of its direct children (`parent` is its `id`)
on the same thread; children on other threads overlap its time and are
not subtracted, as in `unattributed_share`. None where the records carry
no `id`/`parent` or hold no `stage:execute` span."""

from harness import arith, reading


def self_seconds(spans: list, name: str = "stage:execute"):
    """Seconds of the `name` spans less their direct same-thread
    children's; None where there is none or the records carry no `id`."""
    own = {s["id"]: s for s in spans if s["name"] == name and s.get("id")}
    if not own:
        return None
    left = sum(s["dur"] for s in own.values())
    for s in spans:
        top = own.get(s.get("parent"))
        if top is not None and s["tid"] == top["tid"]:
            left -= s["dur"]
    return max(left, 0.0) / 1e6


def read(run: dict):
    w = run["window"]
    return arith.share_pct(self_seconds(w["spans"]), reading.job_seconds(w))
