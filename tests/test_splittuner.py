"""Stage-split cost function: the constant curve, split decisions under a
compile budget, the over-budget path, and the flights-shaped 43-op plan.
The planner end of it (plans that do not move with what was compiled
before) is in tests/test_plan_purity.py."""

import logging

import pytest

from tuplex_tpu.plan import splittuner as ST

# a synthetic accelerator with a steep superlinear compile curve and a
# costly boundary, patched into the constants: the module itself carries
# no curve for any platform but XLA:CPU
ACCEL = "accel"
STEEP_CURVE = (20.0, 1.5, 1.8)
STEEP_BOUNDARY = 0.35


@pytest.fixture()
def accel(monkeypatch):
    monkeypatch.setitem(ST.CURVES, ACCEL, STEEP_CURVE)
    monkeypatch.setitem(ST.BOUNDARY_S, ACCEL, STEEP_BOUNDARY)
    return ACCEL


def test_default_curves_are_superlinear():
    # the flights pathology
    assert ST.predict("cpu", 43) > 2.5 * ST.predict("cpu", 13)
    assert all(c > 1.0 for _, _, c in ST.CURVES.values())
    # no curve was measured to win on the device: none is invented
    assert set(ST.CURVES) == set(ST.BOUNDARY_S) == {"cpu"}
    assert ST.predict("tpu", 13) is None


def test_plan_split_cheap_curve_keeps_fusion(monkeypatch):
    # expensive boundaries, cheap linear compiles: cost-minimizing
    # (prefer_fusion off) still keeps the stage whole
    monkeypatch.setitem(ST.CURVES, "cheap", (0.0, 0.01, 1.0))
    monkeypatch.setitem(ST.BOUNDARY_S, "cheap", 5.0)
    dec = ST.plan_split(20, budget_s=480.0, platform="cheap")
    assert dec.k == 1 and not dec.over_budget
    assert dec.predicted_compile_s == pytest.approx(0.2)


def test_plan_split_flights_within_bench_deadline(accel):
    """Acceptance: flights' 43-op stage under a steep curve predicts a compile
    total inside the bench child deadline — the old maxStageOps=20
    constant predicted 3 segments whose summed compile blew it (which is
    why flights had no TPU bench line)."""
    budget = 480.0                  # tuplex.tpu.compileBudgetS default,
                                    # well under the ~1470s bench child cap
    old = sum(ST.predict(accel, s) for s in (15, 15, 13))  # maxStageOps=20
    assert old > budget             # the status quo ante provably missed
    dec = ST.plan_split(43, budget_s=budget, platform=accel)
    assert not dec.over_budget
    assert dec.k > 3
    assert dec.predicted_compile_s <= budget
    assert "43 ops" in dec.describe()
    assert "predicted compile" in dec.describe()


def test_plan_split_over_budget_takes_the_cheapest(accel):
    dec = ST.plan_split(43, budget_s=10.0, platform=accel)
    assert dec.over_budget
    # over budget everywhere, the CHEAPEST split (min predicted compile)
    # wins, not the finest — the fixed per-executable cost dominates
    # past a point
    assert 1 < dec.k <= 32
    assert dec.predicted_compile_s == pytest.approx(
        min(sum(ST.predict(accel, s) for s in ST._chunk_sizes(43, k))
            for k in range(1, 33)))
    assert "budget 10s" in dec.describe() and "cheapest split" in dec.reason


def test_decision_logged(accel, caplog):
    dec = ST.plan_split(30, budget_s=480.0, platform=accel)
    with caplog.at_level(logging.INFO, logger="tuplex_tpu.plan"):
        ST.log_decision(dec)
    assert any("stage-split" in r.getMessage()
               for r in caplog.records)
    # an over-budget decision logs at WARNING (visible without -v logging)
    caplog.clear()
    bad = ST.plan_split(43, budget_s=10.0, platform=accel)
    with caplog.at_level(logging.WARNING, logger="tuplex_tpu.plan"):
        ST.log_decision(bad)
    assert any(r.levelno == logging.WARNING for r in caplog.records)


def test_platform_without_a_curve_keeps_fusion():
    """A platform without a curve — every accelerator — keeps its stages
    fused whatever the budget, hazard costs or not, and predicts no
    compile seconds."""
    for budget in (480.0, 1.0, 0.0):
        for costs in (None, [500.0] * 43):
            dec = ST.plan_split(43, budget_s=budget, platform="never-seen",
                                op_costs=costs)
            assert dec.k == 1 and dec.per == 43 and not dec.over_budget
            assert dec.predicted_compile_s is None
            assert dec.boundaries is None and "never-seen" in dec.reason
    assert "no compile curve" in dec.describe()
