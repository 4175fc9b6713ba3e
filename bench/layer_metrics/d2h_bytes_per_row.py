"""Transfer: bytes fetched from the device over the window (`xferstats`),
for each input row of the window's jobs."""


def read(run: dict):
    w = run["window"]
    return w["xfer"]["d2h_bytes"] / w["rows"] if w["rows"] else None
