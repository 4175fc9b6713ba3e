"""Stage-split tuner tests: measured curve fit, split decisions under a
compile budget, degrade path, and the flights-shaped 43-op plan."""

import logging

import pytest

from tuplex_tpu.plan import splittuner as ST


@pytest.fixture()
def model_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("TUPLEX_COMPILE_MODEL_DIR", str(tmp_path))
    ST.reset_models()
    yield tmp_path
    ST.reset_models()


# a synthetic accelerator with a steep superlinear compile curve and a
# costly boundary, handed in explicitly: the tuner itself carries no curve
# for a platform it has not observed
ACCEL = "accel"
STEEP_CURVE = (20.0, 1.5, 1.8)
STEEP_BOUNDARY = 0.35


def accel_model():
    return ST.CompileModel(ACCEL, default_curve=STEEP_CURVE,
                           default_boundary=STEEP_BOUNDARY)


@pytest.fixture()
def accel_backend(model_dir, monkeypatch):
    """The process looks like it runs on the synthetic accelerator and
    model_for() hands out its explicit-curve model."""
    from tuplex_tpu.runtime import jaxcfg

    monkeypatch.setattr(jaxcfg.jax, "default_backend", lambda: ACCEL)
    ST._MODELS[ACCEL] = accel_model()
    return ST._MODELS[ACCEL]


def test_default_curves_are_superlinear(model_dir):
    m = accel_model()
    assert m.predict(43) > 2.5 * m.predict(13)     # the flights pathology
    (_, _, c), fitted = m.curve()
    assert not fitted and c > 1.0


def test_power_law_fit_from_observations(model_dir):
    m = ST.CompileModel("cpu")
    for n, s in [(10, 1.0), (10, 1.1), (20, 4.0), (40, 16.0), (80, 64.0)]:
        m.record_compile(n, s)
    (a, b, c), fitted = m.curve()
    assert fitted and a == 0.0
    assert 1.8 < c < 2.2                            # t ~ n^2 synthetic data
    assert 12.0 < m.predict(40) < 20.0
    # persisted: a fresh model instance reloads the fit inputs
    m2 = ST.CompileModel("cpu")
    assert len(m2.obs) == 5
    (_, _, c2), fitted2 = m2.curve()
    assert fitted2 and abs(c2 - c) < 1e-9


def test_boundary_cost_median_and_persistence(model_dir):
    m = ST.CompileModel("cpu")
    default = m.boundary_cost()
    assert default > 0
    for s in (0.2, 0.4, 0.3):
        m.record_boundary(s)
    assert m.boundary_cost() == pytest.approx(0.3)
    assert ST.CompileModel("cpu").boundary_cost() == pytest.approx(0.3)


def test_device_dispatch_cost_feeds_boundary_tax(model_dir):
    """The devprof feed (runtime/devprof.stage_report -> warm median ->
    record_device_dispatch) is the first MEASURED device-cost feature in
    the split decision: every extra segment is one extra device dispatch,
    so its measured occupancy joins the per-boundary tax."""
    m = ST.CompileModel("cpu")
    assert m.device_dispatch_cost() == 0.0           # nothing measured yet
    base = ST.plan_split(12, budget_s=0.0, model=m)
    for s in (0.05, 0.15, 0.10):
        m.record_device_dispatch(s)
    # min: the cheapest observed dispatch proxies the FIXED per-dispatch
    # device overhead (compute splits with the stage, the fixed part
    # is what an extra boundary actually pays)
    assert m.device_dispatch_cost() == pytest.approx(0.05)
    # persists with the model like boundary samples do
    assert ST.CompileModel("cpu").device_dispatch_cost() == \
        pytest.approx(0.05)
    dec = ST.plan_split(12, budget_s=0.0, model=m)
    if dec.k > 1:
        # the tax per boundary is now host boundary + measured device
        unit = m.boundary_cost() + m.device_dispatch_cost()
        assert dec.boundary_s == pytest.approx((dec.k - 1) * unit)
    # a dearer boundary can only push the decision toward FEWER segments
    assert dec.k <= base.k


def test_plan_split_cheap_curve_keeps_fusion(model_dir):
    m = ST.CompileModel("cpu")
    for n, s in [(5, 0.05), (10, 0.1), (20, 0.2)]:
        m.record_compile(n, s)
    m.record_boundary(5.0)          # expensive boundaries, cheap compiles
    # within the observed size range the measured-cheap curve rules
    dec = ST.plan_split(20, budget_s=480.0, model=m)
    assert dec.k == 1 and not dec.degrade


def test_predict_never_extrapolates_below_default(model_dir):
    """Survivorship-bias guard: a fit over small FINISHED compiles must
    not extrapolate the mega-fusion regime change away (the flights 43-op
    stage wedges XLA:CPU but never finishes, so it can never appear in
    the observations) — beyond 1.5x the observed range the prediction
    floors at the default curve."""
    m = ST.CompileModel("cpu")
    for n, s in [(5, 0.05), (10, 0.1), (13, 0.15)]:
        m.record_compile(n, s)
    (_, _, _), fitted = m.curve()
    assert fitted
    assert m.predict(13) < 1.0                       # fit rules in-range
    da, db, dc = ST._DEFAULT_CURVE["cpu"]
    assert m.predict(43) >= da + db * 43 ** dc       # default floors beyond


def test_censored_observations_teach_the_fit(model_dir):
    """A compile that never finishes still teaches the model via the
    watchdog's censored lower bounds — but only ABOVE the finished range
    (a small-n wedge is a per-fingerprint pathology, handled by the
    deadline marker, and must not bend the curve)."""
    m = ST.CompileModel("cpu")
    for n, s in [(5, 1.0), (10, 4.0), (13, 7.0)]:
        m.record_compile(n, s)
    m.record_running(43, 1200.0)            # the wedged mega-fusion
    m.record_running(3, 600.0)              # small-n wedge: ignored by fit
    (_, _, c), fitted = m.curve()
    assert fitted
    assert m.predict(43) >= 1000.0          # lower bound respected
    assert m.predict(5) < 3.0               # small-n wedge didn't bend it
    # persisted: a fresh instance keeps the censored points
    m2 = ST.CompileModel("cpu")
    assert m2.censored.get(43) == pytest.approx(1200.0)


def test_plan_split_flights_within_bench_deadline(model_dir):
    """Acceptance: flights' 43-op stage under the tuner predicts a compile
    total inside the bench child deadline — the old maxStageOps=20
    constant predicted 3 segments whose summed compile blew it (which is
    why flights had no TPU bench line)."""
    m = accel_model()               # fresh: the explicit steep curve
    budget = 480.0                  # tuplex.tpu.compileBudgetS default,
                                    # well under the ~1470s bench child cap
    old = sum(m.predict(s) for s in (15, 15, 13))   # maxStageOps=20 split
    assert old > budget             # the status quo ante provably missed
    dec = ST.plan_split(43, budget_s=budget, model=m)
    assert not dec.degrade
    assert dec.k > 3
    assert dec.predicted_compile_s <= budget
    assert "43 ops" in dec.describe()
    assert "predicted compile" in dec.describe()


def test_plan_split_degrades_over_budget(model_dir):
    m = accel_model()
    dec = ST.plan_split(43, budget_s=10.0, model=m)
    assert dec.degrade
    # degraded stages still take the CHEAPEST split (min predicted
    # compile), not the finest — the fixed per-executable cost dominates
    # past a point
    assert 1 < dec.k <= 32
    assert dec.predicted_compile_s == pytest.approx(
        min(sum(m.predict(s) for s in ST._chunk_sizes(43, k))
            for k in range(1, 33)))
    assert "DEGRADED" in dec.describe()


def test_decision_logged(model_dir, caplog):
    dec = ST.plan_split(30, budget_s=480.0, model=accel_model())
    with caplog.at_level(logging.INFO, logger="tuplex_tpu.plan"):
        ST.log_decision(dec)
    assert any("stage-split tuner" in r.getMessage()
               for r in caplog.records)
    # a degraded decision logs at WARNING (visible without -v logging)
    caplog.clear()
    bad = ST.plan_split(43, budget_s=10.0, model=accel_model())
    with caplog.at_level(logging.WARNING, logger="tuplex_tpu.plan"):
        ST.log_decision(bad)
    assert any(r.levelno == logging.WARNING for r in caplog.records)


def test_split_oversize_uses_tuner_on_accelerator(accel_backend, ctx):
    """On a (simulated) accelerator backend the auto split comes from the
    tuner: segments carry the decision + per-segment predicted compile
    seconds, and the predicted total fits the budget."""
    import tests.test_compilequeue as TC
    from tuplex_tpu.plan import physical as P
    ds = ctx.parallelize(list(range(256)))
    fns = [TC.m1, TC.m2, TC.m4, TC.m5, TC.m6]
    for i in range(25):
        ds = ds.map(fns[i % len(fns)])
    stages = P.plan_stages(ds._op, ctx.options_store)
    segs = [s for s in stages if getattr(s, "ops", None)]
    assert len(segs) > 1, "tuner should split a 25-op accelerator stage"
    dec = segs[0].split_decision
    assert dec is not None and dec.n_ops == 25
    assert dec.predicted_compile_s <= dec.budget_s
    for seg in segs:
        assert seg.predicted_compile_s is not None
        assert not seg.cpu_compile
    # explicit maxStageOps still overrides the tuner
    ctx.options_store.set("tuplex.tpu.maxStageOps", 20)
    stages2 = P.plan_stages(ds._op, ctx.options_store)
    segs2 = [s for s in stages2 if getattr(s, "ops", None)]
    assert max(len(s.ops) for s in segs2) <= 20
    assert all(s.split_decision is None for s in segs2)


def test_split_oversize_degrade_marks_cpu_compile(accel_backend, ctx):
    import tests.test_compilequeue as TC
    from tuplex_tpu.plan import physical as P
    ctx.options_store.set("tuplex.tpu.compileBudgetS", 1)
    ds = ctx.parallelize(list(range(256)))
    fns = [TC.m1, TC.m2, TC.m4, TC.m5, TC.m6]
    for i in range(25):
        ds = ds.map(fns[i % len(fns)])
    stages = P.plan_stages(ds._op, ctx.options_store)
    segs = [s for s in stages if getattr(s, "ops", None)]
    assert segs and all(s.cpu_compile for s in segs)
    assert segs[0].split_decision.degrade


def test_unobserved_platform_keeps_fusion_and_never_degrades(model_dir):
    """A platform nobody has compiled on has NO curve: whatever the budget,
    its stages stay fused and nothing is sent to the host CPU. Real
    observations are then used like on any other platform."""
    m = ST.CompileModel("never-seen")
    assert m.curve() == (None, False)
    assert m.predict(43) == 0.0 and m.boundary_cost() == 0.0
    for budget in (480.0, 1.0, 0.0):
        dec = ST.plan_split(43, budget_s=budget, model=m)
        assert dec.k == 1 and not dec.degrade and not dec.fitted
        assert "never-seen" in dec.reason
    for n, s in ((5, 10.0), (10, 40.0), (20, 160.0)):   # t ~ 0.4 n^2
        m.record_compile(n, s)
    (_, _, c), fitted = m.curve()
    assert fitted and 1.8 < c < 2.2
    dec = ST.plan_split(43, budget_s=480.0, model=m)
    assert dec.fitted and dec.k > 1 and not dec.degrade


def test_unobserved_accelerator_plan_stays_fused(model_dir, monkeypatch,
                                                 ctx):
    """The planner on an accelerator with no compile model: the 25-op stage
    the steep curve splits (above) stays one fused device stage."""
    import tests.test_compilequeue as TC
    from tuplex_tpu.plan import physical as P
    from tuplex_tpu.runtime import jaxcfg

    monkeypatch.setattr(jaxcfg.jax, "default_backend", lambda: "never-seen")
    ctx.options_store.set("tuplex.tpu.compileBudgetS", 1)
    ds = ctx.parallelize(list(range(256)))
    fns = [TC.m1, TC.m2, TC.m4, TC.m5, TC.m6]
    for i in range(25):
        ds = ds.map(fns[i % len(fns)])
    segs = [s for s in P.plan_stages(ds._op, ctx.options_store)
            if getattr(s, "ops", None)]
    assert len(segs) == 1 and not segs[0].cpu_compile
    assert segs[0].split_decision.k == 1
