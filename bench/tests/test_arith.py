import math

import pytest

from harness import arith, peaks


def test_rate_is_work_over_time():
    assert arith.rate(3_000_000, 24.0) == 125_000.0
    with pytest.raises(ValueError):
        arith.rate(10, 0.0)


def test_share_pct():
    assert arith.share_pct(2.5, 10.0) == 25.0
    assert arith.share_pct(None, 10.0) is None
    assert arith.share_pct(1.0, 0.0) is None


def test_roofline_share_counts_every_chip():
    # 819 MB at 819 GB/s is 1 ms; busy for 1 s -> 0.1 %
    one = arith.roofline_share_pct(819e6, 819e9, 1, 1.0)
    assert one == pytest.approx(0.1)
    assert arith.roofline_share_pct(819e6, 819e9, 4, 1.0) == \
        pytest.approx(one / 4)
    assert arith.roofline_share_pct(819e6, 819e9, 1, 0.0) is None
    assert arith.roofline_share_pct(0, 819e9, 1, 1.0) is None


def test_quartile_spread_is_the_contracts_measure():
    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    # statistics.quantiles(n=4) of these: q1 = 100.75, q3 = 104.25
    assert arith.quartile_spread(vals) == pytest.approx(3.5 / 102.5)


def test_finite_names_what_json_cannot_hold():
    assert arith.finite(1.5) == 1.5
    assert arith.finite(float("nan")) == "nan"
    assert arith.finite(math.inf) == "inf"


def test_peaks_table_knows_the_v5e_and_nothing_else():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
