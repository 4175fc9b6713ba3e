"""Device: 1 - the union of device-operation intervals over the traced
job's wall time, from the profiler trace; the mean over the chips used."""


def read(run: dict):
    t = run["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
