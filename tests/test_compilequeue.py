"""Compile-pipeline tests: parallel pool, content-addressed AOT reuse,
isomorphic-stage dedup, cache-key sensitivity, stale-artifact eviction."""

import json
import os
import pickle
import subprocess
import sys
import time

import pytest

import tuplex_tpu
from tuplex_tpu.exec import compilequeue as CQ


# module-level UDFs: reflection needs real source files
def m1(x):
    return x * 2 + 1


def m2(x):
    return x - 3


def m3(x):
    return x * x + 7


def m4(x):
    return x + 100


def m5(x):
    return x // 3


def m6(x):
    return x - 50


K_A = 5
K_B = 7


def add_a(x):
    return x + K_A


def add_b(x):
    return x + K_B


@pytest.fixture()
def fresh_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("TUPLEX_AOT_CACHE", str(tmp_path / "aot"))
    CQ.clear()
    yield str(tmp_path / "aot")
    CQ.clear()


def _plan_and_first_part(ctx, ds):
    from tuplex_tpu.api.dataset import _source_partitions
    from tuplex_tpu.plan.physical import plan_stages

    stages = plan_stages(ds._op, ctx.options_store)
    parts = _source_partitions(ctx, stages[0])
    return stages, parts[0]


def test_parallel_pool_beats_serial_sum(fresh_cache, monkeypatch):
    """Acceptance: a cold plan of >=3 stages compiles all stages
    CONCURRENTLY — wall under 0.6x the serial sum of the individual
    compile times. Latency is injected into the one expensive call
    (_compile_lowered) so the assertion measures pool concurrency, not
    XLA's mood."""
    real = CQ._compile_lowered

    def slow_compile(lowered):
        time.sleep(0.35)
        return real(lowered)

    monkeypatch.setattr(CQ, "_compile_lowered", slow_compile)
    # this test measures POOL concurrency; pin isolation to the thread
    # path so a fork-deadlock kill/retry (tested on its own in
    # test_faults) can't poison the wall-clock assertion on a loaded box
    monkeypatch.setenv("TUPLEX_COMPILE_ISOLATION", "thread")
    ctx = tuplex_tpu.Context({"tuplex.tpu.maxStageOps": 2})
    data = list(range(4096))
    ds = ctx.parallelize(data).map(m1).map(m2).map(m3) \
        .map(m4).map(m5).map(m6)
    stages, first = _plan_and_first_part(ctx, ds)
    n_transform = sum(1 for s in stages if getattr(s, "ops", None))
    assert n_transform >= 3

    snap = CQ.snapshot()
    t0 = time.perf_counter()
    futs = ctx.backend._precompile_driver(stages, first)
    assert len(futs) >= 3
    for f in futs:
        f.result()
    wall = time.perf_counter() - t0
    d = CQ.delta(snap)
    assert d["stage_compiles"] >= 3
    serial_sum = d["compile_s"]          # summed per-compile wall seconds
    assert serial_sum >= 3 * 0.35
    assert wall < 0.6 * serial_sum, \
        f"pool wall {wall:.2f}s vs serial sum {serial_sum:.2f}s"

    # ... and execution finds every executable already built: zero compiles
    snap = CQ.snapshot()
    out = ds.collect()
    assert out == [m6(m5(m4(m3(m2(m1(x)))))) for x in data]
    assert CQ.delta(snap)["stage_compiles"] == 0


_CHILD_SCRIPT = """
import json, sys
sys.path.insert(0, {repo!r})
sys.path.insert(0, {here!r})
import jax
jax.config.update("jax_platforms", "cpu")
import tuplex_tpu
from tuplex_tpu.exec import compilequeue as CQ
from test_compilequeue import m1, m2, m3, m4

ctx = tuplex_tpu.Context({{"tuplex.tpu.maxStageOps": 2}})
data = list(range(2000))
out = ctx.parallelize(data).map(m1).map(m2).map(m3).map(m4).collect()
print(json.dumps({{"rows": out[:5] + out[-5:], "n": len(out),
                  "stats": CQ.snapshot(),
                  "metric_compile_s": ctx.metrics.compileTime(),
                  "metric_compiles": ctx.metrics.stageCompileCount()}}))
"""


def test_aot_reuse_across_processes(fresh_cache, tmp_path):
    """Acceptance: a second PROCESS re-running the same pipeline records
    zero stage compiles — every executable deserializes from the
    content-addressed artifact store (hit counter proves it)."""
    script = tmp_path / "pipe_child.py"
    script.write_text(_CHILD_SCRIPT.format(
        repo=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        here=os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env["TUPLEX_AOT_CACHE"] = fresh_cache
    env.pop("JAX_PLATFORMS", None)

    def run():
        r = subprocess.run([sys.executable, str(script)],
                           capture_output=True, text=True, env=env,
                           timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.splitlines()[-1])

    first = run()
    assert first["stats"]["stage_compiles"] >= 2      # cold: real compiles
    assert first["metric_compile_s"] > 0              # surfaced in metrics
    second = run()
    assert second["stats"]["stage_compiles"] == 0, second["stats"]
    assert second["stats"]["aot_hits"] >= first["stats"]["stage_compiles"]
    assert second["metric_compiles"] == 0
    assert second["rows"] == first["rows"] and second["n"] == first["n"]


_DEVICE_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
import tuplex_tpu
from tuplex_tpu.exec import compilequeue as CQ

assert len(jax.devices()) == 8


def fn(d):
    return {{"y": d["x"] * 3 + 1}}


x = np.arange(64, dtype=np.int64)
one = CQ.aot_jit(fn, tag="one")({{"x": x}})["y"]
mesh = Mesh(np.array(jax.devices()[2:6]), ("data",))
xs = jax.device_put(x, NamedSharding(mesh, P("data")))
four = CQ.aot_jit(fn, salt="/mesh0x4", tag="four")({{"x": xs}})["y"]
print(json.dumps({{
    "one": np.asarray(one).tolist() == (x * 3 + 1).tolist(),
    "one_devices": sorted(d.id for d in one.devices()),
    "four": np.asarray(four).tolist() == (x * 3 + 1).tolist(),
    "four_devices": sorted(d.id for d in four.devices()),
    "stats": CQ.snapshot()}}))
"""


def test_aot_load_runs_on_the_compiled_devices(fresh_cache, tmp_path):
    """Regression (jax 0.9): ``deserialize_and_load`` defaults to EVERY
    device of the backend, so on an 8-device platform a stored one-device
    executable came back as an 8-shard one and the first call raised
    "Expected args to execute_sharded_on_local_devices to have 8 shards"
    — which the backend swallowed, running the stage in the interpreter.
    A second process must load each artifact onto exactly the devices it
    was compiled for: device 0, and a 4-device mesh that is not 0..3."""
    script = tmp_path / "device_child.py"
    script.write_text(_DEVICE_CHILD.format(
        repo=os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    env = dict(os.environ)
    env["TUPLEX_AOT_CACHE"] = fresh_cache

    def run():
        r = subprocess.run([sys.executable, str(script)],
                           capture_output=True, text=True, env=env,
                           timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.splitlines()[-1])

    first = run()
    assert first["stats"]["stage_compiles"] == 2
    second = run()
    for res in (first, second):
        assert res["one"] and res["four"], res
        assert res["one_devices"] == [0]
        assert res["four_devices"] == [2, 3, 4, 5]
    assert second["stats"]["stage_compiles"] == 0, second["stats"]
    assert second["stats"]["aot_hits"] == 2
    assert second["stats"]["aot_errors"] == 0


def test_aot_artifact_for_absent_devices_is_a_miss(fresh_cache):
    """An artifact whose recorded devices this process does not have is a
    plain miss (recompile), never a load onto other devices."""
    import jax
    import numpy as np

    def fn(d):
        return {"y": d["x"] + 7}

    avals = ({"x": jax.ShapeDtypeStruct((32,), np.int64)},)
    compiled = CQ.compile_traced(fn, avals)
    fp = CQ.fingerprint_fn(fn, avals)
    meta = CQ._artifact_meta(compiled)
    assert meta["device_ids"] == [0] and meta["exec_platform"] == "cpu"
    assert [d.id for d in CQ._load_devices(meta)] == [0]
    assert CQ._load_devices(dict(meta, device_ids=[4096])) is None
    assert CQ._load_devices(dict(meta, device_ids=[])) is None
    assert CQ._disk_load(fp) is not None


def test_fingerprint_salt_and_donation_sensitivity(fresh_cache):
    """The cache key must move with anything that changes what the
    executable MEANS: donation spec, packing flag, mesh epoch salt."""
    import jax
    import numpy as np

    def fn(d):
        return {"y": d["x"] * 2}

    avals = ({"x": jax.ShapeDtypeStruct((64,), np.int64)},)
    base = CQ.fingerprint_fn(fn, avals)
    assert base == CQ.fingerprint_fn(fn, avals)             # deterministic
    assert base != CQ.fingerprint_fn(fn, avals, donate_argnums=(0,))
    assert base != CQ.fingerprint_fn(fn, avals, salt="pack")
    assert base != CQ.fingerprint_fn(fn, avals, salt="/mesh1x8")
    assert CQ.fingerprint_fn(fn, avals, salt="/mesh1x8") != \
        CQ.fingerprint_fn(fn, avals, salt="/mesh2x8")       # epoch bump
    # different input avals: different executable
    avals2 = ({"x": jax.ShapeDtypeStruct((128,), np.int64)},)
    assert base != CQ.fingerprint_fn(fn, avals2)

    # the OUTPUT pytree is part of the contract: same computation under a
    # different output key must not share (the stored out_tree would
    # replay the wrong column names)
    def fn_renamed(d):
        return {"z": d["x"] * 2}

    assert base != CQ.fingerprint_fn(fn_renamed, avals)


def test_fingerprint_const_value_sensitivity(fresh_cache, ctx):
    """Two stages identical in STRUCTURE but with different captured
    constant values must not share an executable; identical pipelines over
    different data of the same schema must."""
    from tuplex_tpu.plan.physical import plan_stages, stage_fingerprint

    def fp(ds):
        stages = plan_stages(ds._op, ctx.options_store)
        [st] = [s for s in stages if getattr(s, "ops", None)]
        return stage_fingerprint(st)

    fa = fp(ctx.parallelize(list(range(100))).map(add_a))
    fb = fp(ctx.parallelize(list(range(100))).map(add_b))
    fa2 = fp(ctx.parallelize(list(range(200, 300))).map(add_a))
    assert fa is not None and fb is not None
    assert fa != fb                       # K_A vs K_B: different kernels
    assert fa == fa2                      # isomorphic: same executable


def test_isomorphic_stages_share_one_executable(fresh_cache):
    """In-process dedup: an isomorphic pipeline in a SECOND context (own
    backend, own jit cache — only the process-wide content-addressed store
    is shared) compiles nothing and records a dedup hit."""
    ctx_a = tuplex_tpu.Context()
    ctx_b = tuplex_tpu.Context()
    a = ctx_a.parallelize(list(range(5000))).map(m1).map(m2)
    b = ctx_b.parallelize(list(range(7000, 12000))).map(m1).map(m2)
    snap = CQ.snapshot()
    out_a = a.collect()
    d1 = CQ.delta(snap)
    snap = CQ.snapshot()
    out_b = b.collect()
    d2 = CQ.delta(snap)
    assert out_a == [m2(m1(x)) for x in range(5000)]
    assert out_b == [m2(m1(x)) for x in range(7000, 12000)]
    assert d1["stage_compiles"] >= 1      # cold first pipeline compiled...
    assert d2["stage_compiles"] == 0      # ...the clone reuses it
    assert d2["dedup_hits"] >= 1


def test_compile_deadline_and_negative_cache(fresh_cache, monkeypatch):
    """Compile deadline (now default-on): a compile that exceeds it has
    its forked compile CHILD SIGKILLed and raises CompileTimeout (the
    dispatch side then restarts the stage on one degraded tier), writes
    a content-addressed marker, and every later attempt — including a
    fresh in-process store, i.e. what a new process would see — skips
    instantly instead of re-burning the deadline."""
    import jax
    import numpy as np

    real = CQ._compile_lowered

    def slow_compile(lowered):
        time.sleep(1.2)
        return real(lowered)

    monkeypatch.setattr(CQ, "_compile_lowered", slow_compile)

    def fn(d):
        return {"y": d["x"] * 11}

    avals = ({"x": jax.ShapeDtypeStruct((32,), np.int64)},)
    t0 = time.time()
    with pytest.raises(CQ.CompileTimeout):
        CQ.compile_traced(fn, avals, deadline_s=0.2)
    # the kill happens AT the deadline, not after the sleep finishes
    assert time.time() - t0 < 1.1
    assert CQ.STATS["deadline_timeouts"] == 1
    if CQ.isolation_mode() == "fork":
        assert CQ.STATS["compiles_killed"] == 1
    # the wedge died WITH the child: no in-flight entry lingers for the
    # health watchdog to alarm on (the self-clearing half of the check)
    assert CQ.pending_info()["inflight"] == 0
    # in-process negative cache: immediate skip, no second wait
    t0 = time.time()
    with pytest.raises(CQ.CompileTimeout):
        CQ.compile_traced(fn, avals, deadline_s=0.2)
    assert time.time() - t0 < 0.15
    assert CQ.STATS["deadline_skips"] >= 1
    # the marker is on DISK: a cleared store (fresh process) still skips
    CQ._TIMEOUTS.clear()
    with pytest.raises(CQ.CompileTimeout):
        CQ.compile_traced(fn, avals, deadline_s=5.0)
    # ... but a successful run WITHOUT a deadline (the killed child left
    # no artifact behind — that is the point of the kill) lands the
    # artifact, and the artifact WINS over the marker for every later
    # deadline-bearing caller
    exec_ = CQ.compile_traced(fn, avals, deadline_s=0)
    out = exec_({"x": np.arange(32, dtype=np.int64)})
    assert int(np.asarray(out["y"])[3]) == 33
    exec2 = CQ.compile_traced(fn, avals, deadline_s=5.0)
    assert exec2 is not None
    # no deadline configured: nothing times out
    def fn2(d):
        return {"y": d["x"] * 13}

    assert CQ.compile_traced(fn2, avals, deadline_s=0) is not None


def test_prune_stale_platform_artifacts(tmp_path):
    """Eviction: artifacts for another platform or jax version are
    removed; current-platform artifacts survive."""
    import jax

    d = tmp_path / "store"
    d.mkdir()

    def write(name, platform, jaxver, version=CQ._ARTIFACT_VERSION):
        with open(d / name, "wb") as f:
            pickle.dump({"meta": {"v": version, "platform": platform,
                                  "jax": jaxver, "created": 0.0},
                         "payload": b"", "in_tree": None,
                         "out_tree": None}, f)

    write("aaaa.aot", "tpu", jax.__version__)              # wrong platform
    write("bbbb.aot", jax.default_backend(), "0.0.1")      # wrong jax
    write("cccc.aot", jax.default_backend(), jax.__version__, version=-1)
    write("dddd.aot", jax.default_backend(), jax.__version__)   # current
    (d / "junk.aot").write_bytes(b"not a pickle")          # unreadable
    removed = CQ.prune_stale(str(d))
    assert removed == 4
    assert sorted(os.listdir(d)) == ["dddd.aot"]


def test_compile_seconds_in_context_metrics(fresh_cache):
    """Acceptance: per-stage compile_s appears in Context.metrics (and
    hence the bench JSON, which reads metrics.compileTime())."""
    ctx = tuplex_tpu.Context()
    ds = ctx.parallelize(list(range(3000))).map(m3)
    ds.collect()
    bd = ctx.metrics.stage_breakdown()
    assert any("compile_s" in s for s in bd)
    total = ctx.metrics.compileTime()
    as_dict = ctx.metrics.as_dict()
    assert "compile_s" in as_dict and "stage_compiles" in as_dict
    if ctx.metrics.stageCompileCount():
        assert total > 0


# ---------------------------------------------------------------------------
# one identity a stage, from job to job (counts and text, no timing)
# ---------------------------------------------------------------------------

def _mixed_csv(path, n=1500):
    rows = ["x" + str(i) if i % 11 == 0 else str(i) for i in range(n)]
    with open(path, "w") as f:
        f.write("v,k\n" + "\n".join(f"{r},{i % 3}"
                                   for i, r in enumerate(rows)) + "\n")
    return str(path)


def _stage_and_avals(ctx, path):
    """Stage 0 of the mixed-column pipeline, built anew (new operators,
    higher counter ids), with its dispatch avals."""
    from tuplex_tpu.api.dataset import _source_partitions
    from tuplex_tpu.compiler import stagefn as SF
    from tuplex_tpu.plan.physical import plan_stages

    ds = ctx.csv(path).map(lambda x: (len(str(x["v"])), x["k"] + 1))
    st = plan_stages(ds._op, ctx.options_store)[0]
    part = next(iter(_source_partitions(ctx, st, lazy=False)))
    return st, part.schema, SF.partition_avals(part, "q8")


@pytest.mark.parametrize("general", [False, True],
                         ids=["fast-tier", "general-tier"])
def test_rebuilt_pipeline_traces_to_one_fingerprint(fresh_cache, tmp_path,
                                                    general):
    """The '#err' lattice names operators by position, so the same
    pipeline built twice in one process — and rebuilt from its serialized
    spec — is one jaxpr, one fingerprint, one stored executable."""
    import jax

    from tuplex_tpu.exec.serverless import rebuild_stage, serialize_stage

    ctx = tuplex_tpu.Context()
    path = _mixed_csv(tmp_path / "m.csv")
    fps, ids = [], []
    for _ in range(2):
        st, schema, avals = _stage_and_avals(ctx, path)
        rb = rebuild_stage(serialize_stage(st), ctx.options_store,
                           files=list(st.source.files))
        for s in (st, rb):
            traced = jax.jit(s.build_device_fn(
                schema, general=general)).trace(avals)
            fps.append(CQ.fingerprint_traced(traced))
            ids.append(tuple(op.id for op in s.ops))
    assert len(set(ids)) == 4, ids      # four builds, four sets of ids
    assert len(set(fps)) == 1, fps
    ctx.close()


def _q1_like(ctx, path):
    def fold(a, x):
        return (a[0] + x["v"], a[1] + 1)

    return (ctx.csv(path).filter(lambda x: x["d"] <= "1998-06-01")
            .aggregateByKey(lambda a, b: (a[0] + b[0], a[1] + b[1]),
                            fold, (0, 0), ["k", "j"]))


def test_closed_loop_compiles_its_stage_once(fresh_cache, tmp_path,
                                             monkeypatch):
    """q1's shape (filter + aggregateByKey, stage 0 built packed=False
    under the device handoff), the pipeline rebuilt for every collect():
    after the first job the compile plane does nothing at all."""
    monkeypatch.setenv("TUPLEX_DEVICE_HANDOFF", "1")
    monkeypatch.setenv("TUPLEX_COMPILE_ISOLATION", "thread")
    path = str(tmp_path / "li.csv")
    with open(path, "w") as f:
        f.write("k,j,d,v\n")
        for i in range(6000):
            f.write(f"{'abc'[i % 3]},{'xy'[i % 2]},"
                    f"1998-0{1 + i % 9}-01,{i}\n")
    ctx = tuplex_tpu.Context()
    s0 = CQ.snapshot()
    want = sorted(_q1_like(ctx, path).collect())
    assert len(want) == 6
    s1 = CQ.snapshot()
    for _ in range(4):
        assert sorted(_q1_like(ctx, path).collect()) == want
    later = CQ.delta(s1)
    for k in ("compile_starts", "aot_misses", "traces",
              "prewarm_submitted", "stage_compiles"):
        assert later[k] == 0, (k, later)
    assert later["prewarm_skipped"] >= 4, later
    assert CQ.pending_info()["inflight"] == 0
    whole = CQ.delta(s0)
    assert whole["prewarm_used"] == whole["prewarm_submitted"], whole
    ctx.close()


def _inv(x):
    return 1000 // x["a"]


def test_second_jobs_exceptions_name_its_own_operators(fresh_cache):
    """Job 2 of a Context runs job 1's executable (one stage key), and
    its compiled-path exceptions (off the lattice) as well as its
    interpreter-path ones carry job 2's operator ids; a resolver attached
    in job 2 fires."""
    ctx = tuplex_tpu.Context()
    # 0 raises ZeroDivisionError on the device; the str rows are boxed
    # at ingest and raise TypeError in the interpreter
    data = [(i % 7,) if i % 50 else ("s",) for i in range(1, 3001)]

    def build():
        return ctx.parallelize(data, columns=["a"]).map(_inv)

    seen = []
    for _ in range(2):
        snap = CQ.snapshot()
        ds = build()
        got = ds.collect()
        assert len(got) == sum(1 for (a,) in data if a not in (0, "s"))
        recs = ds._last_exceptions
        names = {r.exc_name for r in recs}
        assert names == {"ZeroDivisionError", "TypeError"}, names
        assert {r.op_id for r in recs} == {ds._op.id}, \
            ({r.op_id for r in recs}, ds._op.id)
        seen.append((ds._op.id, CQ.delta(snap)["traces"]))
    assert seen[0][0] != seen[1][0]
    assert seen[1][1] == 0, seen        # job 2 traced nothing: one stage
    for _ in range(2):
        fixed = build().resolve(ZeroDivisionError, lambda x: -1)
        out = fixed.collect()
        assert out.count(-1) == sum(1 for (a,) in data if a == 0)
        assert fixed.exception_counts() == {
            "TypeError": sum(1 for (a,) in data if a == "s")}
    ctx.close()


def test_speculative_joiners_hold_no_pool_worker(fresh_cache, monkeypatch):
    """Eight speculative submissions of one fingerprint whose compile is
    in flight take no pool worker: a ninth, different compile starts at
    once, and all eight settle with the owner."""
    import threading

    import jax
    import numpy as np

    monkeypatch.setenv("TUPLEX_COMPILE_ISOLATION", "thread")
    gate, entered = threading.Event(), threading.Event()
    real = CQ._compile_lowered

    def held(lowered):
        if "tpx_slow" in lowered.as_text()[:400]:
            entered.set()
            assert gate.wait(120)
        return real(lowered)

    monkeypatch.setattr(CQ, "_compile_lowered", held)

    def tpx_slow(x):
        return x * 3 + 1

    def other(x):
        return x - 7

    aval = jax.ShapeDtypeStruct((64,), np.int64)
    try:
        owner = CQ.submit_compile(tpx_slow, (aval,), deadline_s=0)
        assert entered.wait(120)
        snap = CQ.snapshot()
        joiners = [CQ.submit_compile(tpx_slow, (aval,), deadline_s=0,
                                     prewarm=True) for _ in range(8)]
        ninth = CQ.submit_compile(other, (aval,), deadline_s=0)
        assert ninth.result(timeout=120) is not None
        assert not owner.done() and not any(j.done() for j in joiners)
        d = CQ.delta(snap)
        assert d["prewarm_skipped"] == 8 and d["stage_compiles"] == 1, d
    finally:
        gate.set()
    exe = owner.result(timeout=120)
    assert all(j.result(timeout=120) is exe for j in joiners)


def test_cold_plan_overlaps_stage_one_with_stage_zero(fresh_cache,
                                                      monkeypatch):
    """On a store that lacks stage 1, its compile is in flight (on the
    pool) before stage 0's last partition is collected — and stage 0's
    own executable is nobody's speculation."""
    import threading

    from tuplex_tpu.exec.local import LocalBackend

    monkeypatch.setenv("TUPLEX_COMPILE_ISOLATION", "thread")
    events: list = []
    real = CQ._compile_lowered

    def slow(lowered):
        events.append(("compile", threading.current_thread().name))
        time.sleep(0.3)
        return real(lowered)

    monkeypatch.setattr(CQ, "_compile_lowered", slow)
    real_collect = LocalBackend._collect_partition

    def collect(self, stage, part, *a, **kw):
        out = real_collect(self, stage, part, *a, **kw)
        events.append(("collect", stage.source is not None))
        return out

    monkeypatch.setattr(LocalBackend, "_collect_partition", collect)
    ctx = tuplex_tpu.Context({"tuplex.tpu.maxStageOps": 1,
                              "tuplex.partitionSize": "16KB",
                              "tuplex.tpu.compileDeadlineS": 0})
    data = list(range(8192))
    snap = CQ.snapshot()
    assert ctx.parallelize(data).map(m1).map(m2).collect() \
        == [m2(m1(x)) for x in data]
    last0 = max(i for i, e in enumerate(events) if e == ("collect", True))
    assert sum(1 for e in events[:last0] if e == ("collect", True)) >= 1
    pool = [i for i, e in enumerate(events)
            if e[0] == "compile" and e[1].startswith("tpx-compile-")]
    assert pool and pool[0] < last0, events
    d = CQ.delta(snap)
    assert d["prewarm_submitted"] >= 1, d
    assert d["prewarm_used"] == d["prewarm_submitted"], d
    ctx.close()


def test_driver_asks_the_backend_before_it_submits(fresh_cache):
    """What dispatch already traced (same build key, same batch spec) is
    nobody's to speculate: with the pool off for the job itself, a walk
    of the rebuilt plan afterwards finds every stage traced, stage 1 at
    the avals it predicts from stage 0's, and submits nothing."""
    from tuplex_tpu.api.dataset import _source_partitions
    from tuplex_tpu.plan.physical import plan_stages

    ctx = tuplex_tpu.Context({"tuplex.tpu.maxStageOps": 1,
                              "tuplex.tpu.parallelCompile": False})
    data = list(range(5000))

    def build():
        return ctx.parallelize(data).map(m1).map(m2)

    assert build().collect() == [m2(m1(x)) for x in data]
    stages = plan_stages(build()._op, ctx.options_store)
    assert len(stages) == 2
    parts = _source_partitions(ctx, stages[0], lazy=False)
    snap = CQ.snapshot()
    assert ctx.backend._precompile_driver(stages, parts) == []
    d = CQ.delta(snap)
    assert d["prewarm_submitted"] == 0 and d["pool_jobs"] == 0, d
    # both stages, once for each distinct bucket of the partitions
    assert d["prewarm_skipped"] >= 2 and d["prewarm_skipped"] % 2 == 0, d
    ctx.close()
