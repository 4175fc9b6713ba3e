"""`plan_stages` is a function of the operator graph, the sample, the options
and the platform: nothing on disk and nothing an earlier job left in the
process moves a plan, and no layer under the planner writes its input."""

import ast
import importlib
import json
import os
import subprocess
import sys

import pytest

import tuplex_tpu
from tuplex_tpu.plan import physical as P
from tuplex_tpu.runtime import jaxcfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "tuplex_tpu")

# the synthetic accelerator of the split tests: a platform name the process
# answers to, with no curve of its own
ACCEL = "accel"


def _zillow(ctx, d, build):
    from tuplex_tpu.models import zillow

    path = os.path.join(d, "zillow.csv")
    zillow.generate_csv(path, 300, seed=42)
    return build(ctx.csv(path))


def _tpch(ctx, d, query):
    from tuplex_tpu.models import tpch

    path = os.path.join(d, "lineitem.csv")
    tpch.generate_csv(path, 300, seed=4)
    return query(ctx.csv(path))


def _q19(ctx, d):
    from tuplex_tpu.models import tpch

    pq, lq = os.path.join(d, "part.csv"), os.path.join(d, "li19.csv")
    tpch.generate_q19_csvs(pq, lq, 50, 300)
    return tpch.q19(ctx, pq, lq)


def _flights(ctx, d):
    from tuplex_tpu.models import flights

    perf, carrier, airport = (os.path.join(d, n) for n in
                              ("perf.csv", "carrier.csv", "airport.txt"))
    flights.generate_perf_csv(perf, 300, seed=2)
    flights.generate_carrier_csv(carrier)
    flights.generate_airport_db(airport)
    return flights.build_pipeline(ctx, perf, carrier, airport)


def _nyc311(ctx, d):
    from tuplex_tpu.models import nyc311

    path = os.path.join(d, "311.csv")
    nyc311.generate_csv(path, 300)
    return nyc311.build_pipeline(ctx, path)


def _logs(ctx, d):
    from tuplex_tpu.models import logs

    path = os.path.join(d, "access.log")
    logs.generate_log(path, 300)
    return logs.build_pipeline(ctx.text(path), "strip")


def _models():
    from tuplex_tpu.models import tpch, zillow

    return {
        "zillow-z1": lambda c, d: _zillow(c, d, zillow.build_pipeline),
        "zillow-z2": lambda c, d: _zillow(c, d, zillow.build_pipeline_z2),
        "tpch-q1": lambda c, d: _tpch(c, d, tpch.q1),
        "tpch-q6": lambda c, d: _tpch(c, d, tpch.q6),
        "tpch-q19": _q19,
        "flights": _flights,
        "nyc311": _nyc311,
        "logs": _logs,
    }


def _plan_shape(ds, ctx) -> list:
    """(stage type, structural key, operator count) of every stage."""
    return [(type(s).__name__,
             s.key() if isinstance(s, P.TransformStage) else None,
             len(getattr(s, "ops", None) or ()))
            for s in P.plan_stages(ds._op, ctx.options_store)]


def _plant_steep_model(root: str, platform: str) -> str:
    """A compile model as the planner used to persist it, at the path a
    checkout kept it, steep enough to cut any stage of two operators or
    more: on XLA:CPU (fusion preferred) every size is over the budget, on
    an accelerator (cost minimized) halves are always cheaper and fit."""
    d = os.path.join(root, "compile_model")
    os.makedirs(d, exist_ok=True)
    scale = 600.0 if platform == "cpu" else 10.0
    obs = [[n, scale * n * n] for n in (1, 2, 4, 8, 16)]
    with open(os.path.join(d, f"compile_model_{platform}.json"), "w") as fp:
        json.dump({"platform": platform, "updated": 0.0, "obs": obs,
                   "fam_obs": [], "boundary": [1e-6], "device": [],
                   "censored": {}}, fp)
    return d


@pytest.mark.parametrize("platform", ["cpu", ACCEL])
@pytest.mark.parametrize("model", ["zillow-z1", "zillow-z2", "tpch-q1",
                                   "tpch-q6", "tpch-q19", "flights",
                                   "nyc311", "logs"])
def test_plan_ignores_compile_history(model, platform, tmp_path,
                                      monkeypatch):
    monkeypatch.setattr(jaxcfg, "STATE_ROOT", str(tmp_path / "state"))
    if platform != "cpu":
        monkeypatch.setattr(jaxcfg.jax, "default_backend", lambda: platform)
    ctx = tuplex_tpu.Context({"tuplex.sample.maxDetectionRows": "64"})
    ds = _models()[model](ctx, str(tmp_path))
    before = _plan_shape(ds, ctx)
    assert any(kind == "TransformStage" for kind, _, _ in before)
    planted = _plant_steep_model(jaxcfg.STATE_ROOT, platform)
    # the override the persisted model had, and what a new process would
    # have read at its first plan: neither may reach the planner
    monkeypatch.setenv("TUPLEX_COMPILE_MODEL_DIR", planted)
    from tuplex_tpu.plan import splittuner

    importlib.reload(splittuner)
    assert _plan_shape(ds, ctx) == before
    assert os.listdir(planted) == [f"compile_model_{platform}.json"]


@pytest.mark.parametrize("budget", ["0", "1", "480"])
@pytest.mark.parametrize("platform", ["tpu", "never-seen"])
def test_accelerator_plan_stays_fused_and_unpinned(platform, budget, ctx,
                                                   monkeypatch):
    """25 operators on an accelerator, any budget: one fused device stage,
    no predicted compile, and no way left to pin it to the host CPU at
    plan time."""
    import tests.test_compilequeue as TC

    monkeypatch.setattr(jaxcfg.jax, "default_backend", lambda: platform)
    ctx.options_store.set("tuplex.tpu.compileBudgetS", budget)
    ds = ctx.parallelize(list(range(256)))
    fns = [TC.m1, TC.m2, TC.m4, TC.m5, TC.m6]
    for i in range(25):
        ds = ds.map(fns[i % len(fns)])
    segs = [s for s in P.plan_stages(ds._op, ctx.options_store)
            if getattr(s, "ops", None)]
    assert len(segs) == 1 and len(segs[0].ops) == 25
    dec = segs[0].split_decision
    assert dec.k == 1 and not dec.over_budget
    assert segs[0].predicted_compile_s is None
    assert not segs[0].force_interpret
    assert not hasattr(P.TransformStage, "cpu_compile")


_JOB = """
import json, os, sys
sys.path.insert(0, {root!r})
import tuplex_tpu
from tuplex_tpu.exec import compilequeue as CQ
c = tuplex_tpu.Context()
got = c.parallelize(list(range(64))).map(lambda x: x * 3).collect()
assert got == [x * 3 for x in range(64)], got
assert CQ.STATS["stage_compiles"] >= 1
c.close()
print(json.dumps(sorted(os.listdir(os.path.join({root!r}, ".tuplex_cache")))))
"""


def test_job_leaves_no_plan_state(tmp_path):
    """A job under a fresh state root (the package seen through a link, so
    `.tuplex_cache` falls beside the link) leaves `aot` and `xla` only."""
    root = tmp_path / "checkout"
    root.mkdir()
    os.symlink(PKG, root / "tuplex_tpu")
    script = tmp_path / "job.py"
    script.write_text(_JOB.format(root=str(root)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "TUPLEX_AOT_CACHE",
                        "TUPLEX_COMPILE_MODEL_DIR", "PYTHONPATH")}
    env["TUPLEX_COMPILE_ISOLATION"] = "thread"
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, env=env, cwd=str(tmp_path), timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(r.stdout.splitlines()[-1]) == ["aot", "xla"]


def _imports(path: str) -> set:
    """Absolute dotted names of everything `path` imports from the package,
    relative imports resolved against its own location."""
    with open(path) as fp:
        tree = ast.parse(fp.read())
    pkg = os.path.relpath(os.path.dirname(path), REPO).split(os.sep)
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = pkg[:len(pkg) - node.level + 1] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            out.add(mod)
            out.update(f"{mod}.{a.name}" for a in node.names)
    return out


def test_lower_layers_do_not_import_the_planner():
    """`runtime/` imports nothing of `plan/`; `exec/`, `history/` and
    `compiler/` (which rightly read the plan's stages) nothing of the
    split cost function: the planner's inputs are not written from
    below."""
    def offenders(layer, banned):
        found = []
        for dirpath, _, files in os.walk(os.path.join(PKG, layer)):
            for f in files:
                if f.endswith(".py"):
                    path = os.path.join(dirpath, f)
                    hits = sorted(i for i in _imports(path)
                                  if i == banned
                                  or i.startswith(banned + "."))
                    if hits:
                        found.append((os.path.relpath(path, REPO), hits))
        return found

    assert offenders("runtime", "tuplex_tpu.plan") == []
    for layer in ("exec", "history", "compiler"):
        assert offenders(layer, "tuplex_tpu.plan.splittuner") == []
    # the scan sees what it should: exec/ does import plan.physical
    assert offenders("exec", "tuplex_tpu.plan.physical")
