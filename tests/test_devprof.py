"""Device-plane cost attribution (runtime/devprof): roofline math,
StageCost harvest + sidecar persistence (incl. the 2-process AOT
round-trip: analysis present, zero compiles), measured dispatch time in
stage metrics, Prometheus exposition schema for the new families, the
zero-alloc disabled path, and the zillow smoke (scripts/devprof_smoke.py)
tier-1 wiring."""

import json
import os
import subprocess
import sys

import pytest

import tuplex_tpu
from tuplex_tpu.exec import compilequeue as CQ
from tuplex_tpu.runtime import devprof as DP
from tuplex_tpu.runtime import telemetry as T

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# module-level UDFs: reflection needs real source files
def dbl(x):
    return x["v"] * 2 + 1


@pytest.fixture(autouse=True)
def _fresh_devprof():
    DP.clear()
    DP.enable(True)
    yield
    DP.clear()
    DP.enable(True)


@pytest.fixture()
def fresh_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("TUPLEX_AOT_CACHE", str(tmp_path / "aot"))
    CQ.clear()
    yield str(tmp_path / "aot")
    CQ.clear()


# ---------------------------------------------------------------------------
# roofline math: known flops/bytes/time -> exact fractions
# ---------------------------------------------------------------------------

PEAKS = DP.Peaks(flops_per_s=1e12, bytes_per_s=1e11, name="t", kind="table")


def test_roofline_compute_bound_exact():
    # intensity 100 flops/byte >> ridge (10): the compute roof binds.
    # 1e10 flops in 0.1 s = 1e11 FLOP/s achieved = 10% of the 1e12 peak.
    r = DP.roofline(1e10, 1e8, 0.1, peaks=PEAKS)
    assert r["arithmetic_intensity"] == pytest.approx(100.0)
    assert r["attainable_flops_per_s"] == pytest.approx(1e12)
    assert r["roofline_frac"] == pytest.approx(0.1)


def test_roofline_memory_bound_exact():
    # intensity 0.1 flops/byte << ridge: attainable = 0.1 * 1e11 = 1e10.
    # achieved 1e8/0.1s = 1e9 FLOP/s -> exactly 10% of attainable.
    r = DP.roofline(1e8, 1e9, 0.1, peaks=PEAKS)
    assert r["arithmetic_intensity"] == pytest.approx(0.1)
    assert r["attainable_flops_per_s"] == pytest.approx(1e10)
    assert r["roofline_frac"] == pytest.approx(0.1)
    assert r["achieved_bytes_per_s"] == pytest.approx(1e10)


def test_roofline_flop_free_reads_bandwidth_roof():
    # a pure data-movement stage: 5e9 bytes in 0.5 s = 1e10 B/s = 10%
    # of the 1e11 B/s bandwidth peak; intensity reads 0
    r = DP.roofline(0.0, 5e9, 0.5, peaks=PEAKS)
    assert r["arithmetic_intensity"] == 0.0
    assert r["roofline_frac"] == pytest.approx(0.1)
    assert "achieved_flops_per_s" not in r


def test_roofline_clamps_and_rejects_garbage():
    # a bad peak estimate must clamp at 1.0, never report >100%
    tiny = DP.Peaks(flops_per_s=1.0, bytes_per_s=1.0)
    assert DP.roofline(1e9, 1e9, 0.1, peaks=tiny)["roofline_frac"] == 1.0
    assert DP.roofline(1e9, 1e9, 0.0, peaks=PEAKS) == {}
    assert DP.roofline(1e9, 1e9, float("nan"), peaks=PEAKS) == {}
    assert DP.roofline(0.0, 0.0, 1.0, peaks=PEAKS) == {}


def test_platform_peaks_env_override(monkeypatch):
    monkeypatch.setenv("TUPLEX_DEVPROF_PEAKS", "2e12,3e11")
    DP.clear()          # drops the peaks cache
    p = DP.platform_peaks()
    assert p.flops_per_s == 2e12 and p.bytes_per_s == 3e11
    assert p.kind == "override"


class _FakeDev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("kind,flops,bw", [
    ("TPU v5 lite", 197e12, 819e9),      # what a v5e reports
    ("TPU v5e", 197e12, 819e9),
    ("TPU v4", 275e12, 1228e9),
])
def test_platform_peaks_known_tpu_from_the_table(monkeypatch, kind, flops,
                                                 bw):
    import jax

    monkeypatch.delenv("TUPLEX_DEVPROF_PEAKS", raising=False)
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeDev("tpu", kind)])
    DP.clear()
    p = DP.platform_peaks()
    assert (p.flops_per_s, p.bytes_per_s, p.kind) == (flops, bw, "table")
    DP.clear()


def test_unknown_accelerator_gets_no_peaks_and_no_roofline_share(
        monkeypatch):
    """An accelerator that is not in _TPU_PEAKS used to be handed v2's
    peaks as an 'estimate'. It has no roof here: labeled unknown, achieved
    rates only, no share of anything."""
    import jax

    monkeypatch.delenv("TUPLEX_DEVPROF_PEAKS", raising=False)
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDev("tpu", "TPU v9 mystery")])
    DP.clear()
    p = DP.platform_peaks()
    assert p.kind == "unknown" and p.name == "tpu v9 mystery"
    assert p.flops_per_s == 0.0 and p.bytes_per_s == 0.0
    r = DP.roofline(1e10, 1e8, 0.1)
    assert r == {"achieved_flops_per_s": 1e11, "achieved_bytes_per_s": 1e9}
    assert DP.roofline(0.0, 5e9, 0.5) == {"achieved_bytes_per_s": 1e10}
    DP.clear()


# ---------------------------------------------------------------------------
# StageCost harvest + sidecar persistence
# ---------------------------------------------------------------------------

def test_harvest_real_compiled_executable():
    import jax
    import jax.numpy as jnp

    c = jax.jit(lambda x: jnp.sin(x) @ x.T).trace(
        jax.ShapeDtypeStruct((64, 64), "float32")).lower().compile()
    cost = DP.harvest(c)
    assert cost is not None
    assert cost.flops > 0 and cost.bytes_accessed > 0
    assert cost.argument_bytes > 0 and cost.output_bytes > 0
    assert cost.peak_bytes >= cost.argument_bytes + cost.output_bytes
    # round-trips through the JSON sidecar shape
    again = DP.StageCost.from_dict(
        json.loads(json.dumps(cost.to_dict())))
    assert again == cost


def test_sidecar_roundtrip_and_note_compiled(fresh_cache):
    import jax
    import jax.numpy as jnp

    c = jax.jit(lambda x: x * 2.0).trace(
        jax.ShapeDtypeStruct((128,), "float32")).lower().compile()
    DP.note_compiled("tagA", "fp123", c)
    path = os.path.join(fresh_cache, "fp123.cost.json")
    assert os.path.exists(path), "sidecar not persisted next to artifact"
    stored = DP.load_cost("fp123")
    assert stored is not None and stored.flops == DP.cost_for_tag("tagA").flops
    # a second tag sharing the fingerprint (dedup hit) maps for free
    DP.note_tag("tagB", "fp123")
    assert DP.cost_for_tag("tagB") == stored
    # a fresh registry recovers the analysis FROM THE SIDECAR, without
    # touching the executable (None stands in for it)
    DP.clear()

    class _Boom:
        def cost_analysis(self):
            raise AssertionError("sidecar should have answered")

        memory_analysis = cost_analysis

    DP.note_compiled("tagA", "fp123", _Boom())
    assert DP.cost_for_tag("tagA") == stored


def test_backend_returning_nothing_recorded_as_unavailable(fresh_cache):
    class _Nothing:
        def cost_analysis(self):
            return None

        def memory_analysis(self):
            raise RuntimeError("unimplemented")

    assert DP.harvest(_Nothing()) is None
    DP.note_compiled("tagN", "fpN", _Nothing())
    assert DP.tag_seen("tagN")
    assert DP.cost_for_tag("tagN") is None
    # the compilestats line flags it instead of printing blanks
    from tuplex_tpu.utils.compilestats import _cost_line

    line = _cost_line({"analysis": None, "device_s_per_dispatch": 0.002})
    assert "UNAVAILABLE" in line
    assert _cost_line(None) is None


# ---------------------------------------------------------------------------
# end to end: stage metrics + exposition + stage index
# ---------------------------------------------------------------------------

def _tiny_pipeline(ctx):
    return ctx.parallelize([(i,) for i in range(4000)],
                           columns=["v"]).map(dbl)


def test_stage_metrics_carry_device_cost(fresh_cache):
    ctx = tuplex_tpu.Context({"tuplex.partitionSize": "64KB"})
    out = _tiny_pipeline(ctx).collect()
    assert out == [i * 2 + 1 for i in range(4000)]
    m = next(s for s in ctx.metrics.stage_breakdown()
             if "device_s" in s)
    assert m["device_s"] > 0 and m["device_dispatches"] >= 1
    assert m["flops"] > 0 and m["device_bytes"] > 0
    assert m["hbm_peak"] > 0
    assert 0.0 < m["roofline_frac"] <= 1.0
    # peak footprint vs the job's MemoryManager budget
    assert 0.0 < m["hbm_budget_frac"] < 1.0
    assert ctx.metrics.deviceTime() > 0
    assert ctx.metrics.as_dict()["device_s"] > 0
    assert ctx.metrics.hbmPeak() == m["hbm_peak"]
    # the span attrs ride stage:execute when tracing is on (checked via
    # the report snapshot here; trace export covered in test_tracing)
    reps = DP.reports()
    assert any(r.get("device_s", 0) > 0 for r in reps.values())
    # the persisted stage index compilestats queries
    idx = DP.load_stage_index()
    assert any(e.get("analysis") for e in idx.values()), idx


def test_prometheus_exposition_devprof_families(fresh_cache):
    from test_telemetry import _lint_exposition

    T.registry().clear()
    T.enable(True)
    ctx = tuplex_tpu.Context({"tuplex.partitionSize": "64KB"})
    _tiny_pipeline(ctx).collect()
    text = T.render_prometheus()
    parsed = _lint_exposition(text)
    for fam in ("tuplex_devprof_stage_device_seconds",
                "tuplex_devprof_stage_dispatches",
                "tuplex_devprof_stage_flops",
                "tuplex_devprof_stage_bytes",
                "tuplex_devprof_stage_hbm_peak_bytes",
                "tuplex_devprof_stage_roofline_frac"):
        assert parsed["typed"][fam] == "gauge", fam
        assert any('stage="' in lbl
                   for lbl, _ in parsed["samples"][fam]), fam
    assert parsed["typed"]["tuplex_device_dispatch_seconds"] == "histogram"
    states = {lbl for lbl, _ in
              parsed["samples"]["tuplex_device_dispatch_seconds_count"]}
    assert any('state="cold"' in s for s in states)
    T.registry().clear()


def test_cold_warm_split(fresh_cache):
    T.registry().clear()
    T.enable(True)
    ctx = tuplex_tpu.Context({"tuplex.partitionSize": "64KB"})
    ds = _tiny_pipeline(ctx)
    ds.collect()           # cold: first spec call spans the compile wait
    ds.collect()           # warm re-dispatches
    hists = T.registry().histograms()
    by_state: dict = {}
    for (name, lk), h in hists.items():
        if name == "device_dispatch_seconds":
            by_state[dict(lk).get("state")] = \
                by_state.get(dict(lk).get("state"), 0) + h.count
    assert by_state.get("cold", 0) >= 1
    assert by_state.get("warm", 0) >= 1, by_state
    cold = [s for s in ctx.metrics.stages if s.get("device_cold_s", 0) > 0]
    warm = [s for s in ctx.metrics.stages
            if "device_s" in s
            and s["device_s"] > s.get("device_cold_s", 0)]
    assert cold and warm
    T.registry().clear()


# ---------------------------------------------------------------------------
# persistence round-trip: 2nd process = analysis present, ZERO compiles
# ---------------------------------------------------------------------------

_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
sys.path.insert(0, {here!r})
import jax
jax.config.update("jax_platforms", "cpu")
import tuplex_tpu
from tuplex_tpu.exec import compilequeue as CQ
from test_devprof import dbl

ctx = tuplex_tpu.Context({{"tuplex.partitionSize": "64KB"}})
out = ctx.parallelize([(i,) for i in range(4000)],
                      columns=["v"]).map(dbl).collect()
assert out == [i * 2 + 1 for i in range(4000)]
m = next(s for s in ctx.metrics.stage_breakdown() if "device_s" in s)
print(json.dumps({{"stats": CQ.snapshot(),
                  "flops": m["flops"], "hbm_peak": m["hbm_peak"],
                  "roofline_frac": m["roofline_frac"],
                  "device_s": m["device_s"]}}))
"""


def test_cost_survives_aot_store_across_processes(fresh_cache, tmp_path):
    """The tentpole acceptance: a warm second process deserializes the
    executable (zero compiles) AND recovers the full cost analysis from
    the sidecar persisted alongside the artifact."""
    script = tmp_path / "devprof_child.py"
    script.write_text(_CHILD.format(
        repo=REPO, here=os.path.join(REPO, "tests")))
    env = dict(os.environ)
    env["TUPLEX_AOT_CACHE"] = fresh_cache
    env.pop("JAX_PLATFORMS", None)
    env.pop("TUPLEX_DEVPROF", None)

    def run():
        r = subprocess.run([sys.executable, str(script)],
                           capture_output=True, text=True, env=env,
                           timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.splitlines()[-1])

    first = run()
    assert first["stats"]["stage_compiles"] >= 1
    assert first["flops"] > 0
    sidecars = [f for f in os.listdir(fresh_cache)
                if f.endswith(".cost.json")]
    assert sidecars, "no cost sidecar persisted alongside the artifacts"
    second = run()
    assert second["stats"]["stage_compiles"] == 0, second["stats"]
    assert second["stats"]["aot_hits"] >= 1
    assert second["flops"] == first["flops"]
    assert second["hbm_peak"] == first["hbm_peak"]
    assert 0.0 < second["roofline_frac"] <= 1.0


# ---------------------------------------------------------------------------
# disabled path: no samples, no allocation
# ---------------------------------------------------------------------------

def test_disabled_records_nothing_and_allocates_nothing():
    DP.enable(False)
    DP.record_dispatch("tag", 0.5, cold=False, rows=10)
    assert DP.reports() == {} and not DP.tag_seen("tag")
    import tracemalloc

    for _ in range(64):               # warm lazy caches
        DP.record_dispatch("tag", 0.5)
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(10000):
        DP.record_dispatch("tag", 0.5)
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(s.size_diff for s in after.compare_to(before, "lineno")
                if s.size_diff > 0 and any(
                    (f.filename or "").replace(os.sep, "/")
                    .endswith("runtime/devprof.py")
                    for f in s.traceback))
    # a PER-CALL allocation would show as >= 10000 x alloc-size (tens of
    # KB); a few hundred bytes is tracemalloc/interned-object noise
    assert grown < 2048, \
        f"disabled record_dispatch allocated {grown} bytes/10k calls"


def test_env_kill_switch_wins(monkeypatch):
    monkeypatch.setenv("TUPLEX_DEVPROF", "0")
    DP.enable(True)                    # option says on; env must win
    assert not DP.enabled()
    monkeypatch.delenv("TUPLEX_DEVPROF")
    DP.enable(True)
    assert DP.enabled()


# ---------------------------------------------------------------------------
# tier-1 wiring of the zillow smoke (like scripts/trace_smoke.py)
# ---------------------------------------------------------------------------

def test_devprof_smoke_zillow():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "devprof_smoke.py")],
        capture_output=True, text=True, timeout=580,
        env={**{k: v for k, v in os.environ.items()
                if k != "TUPLEX_DEVPROF"}, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "devprof-smoke OK" in out.stdout
