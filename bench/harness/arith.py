"""The arithmetic of the metrics, apart from any clock or program."""

from __future__ import annotations

import math
import statistics


def rate(units: float, seconds: float) -> float:
    """Work over time; a window with no time is an error, not a rate."""
    if seconds <= 0:
        raise ValueError(f"rate over {seconds} s")
    return units / seconds


def share_pct(part: float, whole: float):
    """`part` as a percentage of `whole`; None where there is no whole."""
    if whole is None or part is None or whole <= 0:
        return None
    return 100.0 * part / whole


def roofline_share_pct(bytes_touched: float, peak_bytes_per_s: float,
                       chips: int, busy_s: float):
    """Least time the chips need to move `bytes_touched` at their memory
    peak, over the time they were busy. None where nothing ran."""
    if not busy_s or busy_s <= 0 or bytes_touched <= 0:
        return None
    least_s = bytes_touched / (peak_bytes_per_s * chips)
    return 100.0 * least_s / busy_s


def quartile_spread(values: list) -> float:
    """Distance between the first and third quartile as a share of the
    median (`statistics.quantiles(values, n=4)`), the measure the bounds
    are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def finite(x):
    """A JSON-safe number: a non-finite reading becomes its name."""
    if isinstance(x, float) and not math.isfinite(x):
        return repr(x)
    return x
