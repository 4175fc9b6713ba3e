"""Jaxpr-plane static analysis (compiler/graphlint): the eqn census and
hazard score, the pinned ``wide-str-compaction`` wedge rule (fires on
the flights airport build side, never on a clean stage), the zero-alloc
disabled path, the compile-plane veto (CompileHazard + content-addressed
``.hazard`` marker), construct-weighted split planning (plan/splittuner
op_costs), the static peak-memory vs executor budget plan-time remedy,
and the zero-false-positive smoke (scripts/graphlint_smoke.py) tier-1
wiring."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tuplex_tpu
from tuplex_tpu.compiler import graphlint as GL
from tuplex_tpu.exec import compilequeue as CQ

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_graphlint():
    GL.enable(True)
    GL.set_hazard_threshold(GL._DEFAULT_THRESHOLD)
    yield
    GL.enable(True)
    GL.set_hazard_threshold(GL._DEFAULT_THRESHOLD)


@pytest.fixture()
def fresh_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("TUPLEX_AOT_CACHE", str(tmp_path / "aot"))
    CQ.clear()
    yield
    CQ.clear()


# ---------------------------------------------------------------------------
# analyzer core
# ---------------------------------------------------------------------------

def _clean_fn(arrays):
    x = arrays["a"].astype(jnp.float32)
    return {"out": x * 2.0 + 1.0}


def _wedge_fn(arrays):
    # synthetic carrier of the pinned signature: >=300 eqns for one op,
    # >=10 cumsum compaction eqns, >=4 wide uint8 (string) row buffers
    outs = {}
    for i, (k, v) in enumerate(sorted(arrays.items())):
        x = v.astype(jnp.int32)
        for _ in range(3):
            x = jnp.cumsum(x, axis=1)
        for j in range(80):
            x = x + j
        outs[k] = (x % 251).astype(jnp.uint8)
    return outs


def _strs(n=4):
    return {f"s{i}": jnp.zeros((8, 16), jnp.uint8) for i in range(n)}


def test_analyze_census_and_score():
    closed = jax.make_jaxpr(_clean_fn)({"a": jnp.zeros((8, 4), jnp.int32)})
    rep = GL.analyze(closed, n_ops=2, platform="cpu")
    assert rep is not None and rep.n_eqns >= 2 and rep.n_ops == 2
    assert rep.hazard_score > 0.0
    assert rep.worst_severity() in ("", "info")
    assert not rep.wedge
    # census counted every eqn, families partition the census
    assert sum(rep.census.values()) == rep.n_eqns
    assert sum(rep.families.values()) == rep.n_eqns


def test_wedge_rule_fires_on_pinned_signature_cpu_only():
    closed = jax.make_jaxpr(_wedge_fn)(_strs())
    rep = GL.analyze(closed, n_ops=1, platform="cpu")
    assert rep is not None and rep.wedge
    rules = {f.rule for f in rep.findings if f.severity == "wedge"}
    assert rules == {"wide-str-compaction"}
    assert rep.hazard_score >= 1e9          # wedge forces a veto score
    # the wedge is an XLA:CPU emission pathology — TPU must not fire
    rep_tpu = GL.analyze(closed, n_ops=1, platform="tpu")
    assert rep_tpu is not None and not rep_tpu.wedge


def test_wedge_rule_needs_all_three_axes():
    # same graph, many ops: eqns/op below the density floor -> clean
    closed = jax.make_jaxpr(_wedge_fn)(_strs())
    assert not GL.analyze(closed, n_ops=50, platform="cpu").wedge
    # few string buffers -> clean even at full density
    assert not GL.analyze(jax.make_jaxpr(_wedge_fn)(_strs(2)),
                          n_ops=1, platform="cpu").wedge


def test_disabled_gate_returns_none():
    closed = jax.make_jaxpr(_clean_fn)({"a": jnp.zeros((8, 4), jnp.int32)})
    GL.enable(False)
    assert not GL.enabled()
    assert GL.analyze(closed, n_ops=1, platform="cpu") is None
    GL.enable(True)
    assert GL.analyze(closed, n_ops=1, platform="cpu") is not None


def test_env_kill_switch_wins(monkeypatch):
    monkeypatch.setenv("TUPLEX_GRAPHLINT", "0")
    GL.enable(True)     # option-driven enable must NOT override the env
    assert not GL.enabled()
    monkeypatch.delenv("TUPLEX_GRAPHLINT")
    GL.enable(True)
    assert GL.enabled()


def test_apply_options_threshold_and_gate():
    ctx = tuplex_tpu.Context({"tuplex.tpu.hazardThreshold": "123",
                              "tuplex.sample.maxDetectionRows": "64"})
    try:
        assert GL.hazard_threshold() == 123.0
        assert GL.enabled()
    finally:
        ctx.close()


def test_peak_bytes_scales_with_rows():
    closed = jax.make_jaxpr(_clean_fn)({"a": jnp.zeros((8, 4), jnp.int32)})
    rep = GL.analyze(closed, n_ops=1, platform="cpu")
    assert rep.traced_rows == 8
    assert rep.input_row_bytes > 0
    # the row-linear part of the peak grows 100x with 100x the rows
    assert rep.peak_bytes_at(800) - rep.peak_fixed_bytes == \
        100 * (rep.peak_bytes_at(8) - rep.peak_fixed_bytes)


# ---------------------------------------------------------------------------
# compile-plane veto (exec/compilequeue)
# ---------------------------------------------------------------------------

def test_compile_plane_veto_writes_marker_and_negative_caches(fresh_cache):
    traced = jax.jit(_wedge_fn).trace(_strs())
    fp = "feedc0de" * 5
    with pytest.raises(CQ.CompileHazard):
        CQ._graphlint_vet(traced, fp, "stagetag", 1)
    rec = CQ.read_marker(CQ._artifact_path(fp), "hazard")
    assert rec is not None and rec["rule"] == "wide-str-compaction"
    # second submission: the in-process negative cache answers without
    # re-tracing (and still refuses)
    with pytest.raises(CQ.CompileHazard):
        CQ._graphlint_vet(traced, fp, "stagetag", 1)
    ms, found, avoided = CQ.consume_graphlint("stagetag")
    assert found == 1 and avoided == 2 and ms > 0.0


def test_compile_plane_clean_stage_returns_report(fresh_cache):
    traced = jax.jit(_clean_fn).trace({"a": jnp.zeros((8, 4), jnp.int32)})
    rep = CQ._graphlint_vet(traced, "c0ffee00" * 5, "cleantag", 1)
    assert rep is not None and not rep.wedge
    assert CQ.read_marker(CQ._artifact_path("c0ffee00" * 5),
                          "hazard") is None


def test_compile_hazard_is_a_compile_timeout():
    # the veto rides the existing deadline-degrade tier ladder
    assert issubclass(CQ.CompileHazard, CQ.CompileTimeout)


# ---------------------------------------------------------------------------
# construct-weighted split planning (plan/splittuner, satellite 1)
# ---------------------------------------------------------------------------

# the synthetic platform's op-count curve, patched into the constants: a
# platform without a curve keeps every stage fused, hazard costs or not
TESTONLY_CURVE = (20.0, 1.5, 1.8)


@pytest.fixture()
def testonly(monkeypatch):
    from tuplex_tpu.plan import splittuner as ST

    monkeypatch.setitem(ST.CURVES, "testonly", TESTONLY_CURVE)
    return "testonly"


def test_scatter_heavy_splits_differently_than_elementwise(testonly):
    from tuplex_tpu.plan import splittuner as ST

    # budget above the op-count curve's fused prediction for 12 ops, so
    # the construct mix — not the curve — decides the split
    budget = 2.0 * ST.predict(testonly, 12)
    # equal op count, wildly different construct mix: 12 elementwise ops
    # stay fused, 12 scatter-heavy ops (hazard cost >> budget per op)
    # must split — op-count-only planning cannot tell them apart
    elementwise = ST.plan_split(12, budget, testonly, prefer_fusion=True,
                                op_costs=[0.01] * 12)
    scatter_heavy = ST.plan_split(12, budget, testonly, prefer_fusion=True,
                                  op_costs=[budget / 2.5] * 12)
    assert elementwise.k == 1
    assert scatter_heavy.k > 1
    assert scatter_heavy.k != elementwise.k
    # the decision records that hazard cost (not the op-count curve)
    # picked the split, and where the cuts landed
    assert "hazard" in scatter_heavy.reason
    assert scatter_heavy.boundaries is not None
    assert 0 < len(scatter_heavy.boundaries) == scatter_heavy.k - 1


def test_hazard_split_bounds_worst_segment(testonly):
    from tuplex_tpu.plan import splittuner as ST

    costs = [1.0, 1.0, 20.0, 1.0, 1.0, 1.0]
    dec = ST.plan_split(6, 25.0, testonly, prefer_fusion=True,
                        op_costs=costs)
    # worst single segment must fit the per-segment budget
    if dec.k > 1 and dec.boundaries:
        cuts = [0] + list(dec.boundaries) + [6]
        worst = max(sum(costs[a:b]) for a, b in zip(cuts, cuts[1:]))
        assert worst <= 25.0


# ---------------------------------------------------------------------------
# static peak-memory vs MemoryManager budget (plan plane, satellite 2)
# ---------------------------------------------------------------------------

def test_tiny_executor_memory_degrades_at_plan_time(tmp_path):
    from tuplex_tpu.models import zillow
    from tuplex_tpu.plan.physical import TransformStage, plan_stages

    data = str(tmp_path / "z.csv")
    zillow.generate_csv(data, 120, seed=4)
    ctx = tuplex_tpu.Context({
        "tuplex.sample.maxDetectionRows": "64",
        "tuplex.partitionSize": "256KB",
        # far below any stage's static intermediate peak
        "tuplex.executorMemory": "64KB",
    })
    try:
        ds = zillow.build_pipeline(ctx.csv(data))
        stages = [s for s in plan_stages(ds._op, ctx.options_store)
                  if isinstance(s, TransformStage)]
        # the check's record rides the stage it kept, or each segment
        # of the stage it split
        flagged = [s for s in stages
                   if getattr(s, "static_peak", {}).get("remedy",
                                                        "none") != "none"]
        assert flagged, "no stage hit the static peak-memory gate"
        for s in flagged:
            # the plan-time remedy: either the interpreter (streams
            # rows) or a split tightened below the tuner's own pick
            assert s.force_interpret or \
                s.static_peak["remedy"].startswith("split to <=")
            assert s.static_peak["peak_bytes"] > 64 << 10
        # and the pipeline still completes correctly (no device OOM)
        got = ds.collect()
        assert got == zillow.run_reference_python(data)
    finally:
        ctx.close()


# ---------------------------------------------------------------------------
# tier-1 wiring of the zero-false-positive smoke
# ---------------------------------------------------------------------------

def test_graphlint_smoke_zero_false_positives():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "graphlint_smoke.py")],
        capture_output=True, text=True, timeout=580,
        env={**{k: v for k, v in os.environ.items()
                if k != "TUPLEX_GRAPHLINT"}, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "graphlint-smoke OK" in out.stdout
