"""Transfer: bytes uploaded to the device over the window (`xferstats`),
for each input row of the window's jobs."""


def read(run: dict):
    w = run["window"]
    return w["xfer"]["h2d_bytes"] / w["rows"] if w["rows"] else None
