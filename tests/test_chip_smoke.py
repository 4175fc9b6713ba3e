"""chip_smoke.py's contract that needs no chip: it refuses to start off a
TPU, its record check fails — naming the stage — when work leaves the
device, and jaxcfg puts the caches where the smoke reports them."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _run(args, env_extra=None, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, env=env, cwd=REPO, timeout=300)


def test_refuses_to_start_without_a_tpu():
    r = _run([os.path.join(REPO, "chip_smoke.py")],
             {"JAX_PLATFORMS": "cpu"})
    assert r.returncode not in (0, 3), r.stdout
    assert r.stdout == ""            # no result line, no phase line
    assert "no TPU" in r.stderr


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo there is no program to drive: non-zero, nothing on stdout."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse", "--rows", "100"],
        capture_output=True, text=True, cwd=tmp_path, timeout=300,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.fixture(autouse=True)
def _no_fork_compiles(monkeypatch):
    """On the chip the compile queue never forks (isolation 'thread' off
    the CPU) and the smoke asserts so; hold XLA:CPU to the same here."""
    from tuplex_tpu.exec import compilequeue as CQ

    monkeypatch.setenv("TUPLEX_COMPILE_ISOLATION", "thread")
    monkeypatch.setitem(CQ.STATS, "subprocess_compiles", 0)


def _job(ctx, data):
    from tuplex_tpu.plan.physical import plan_stages

    ds = ctx.parallelize(data).map(lambda x: x * 2)
    planned = plan_stages(ds._op, ctx.options_store)
    n0, f0 = len(ctx.metrics.stages), len(ctx.backend.failure_log)
    got = ds.collect()
    return got, planned, ctx.metrics.stages[n0:], \
        ctx.backend.failure_log[f0:]


def test_record_check_passes_a_clean_run(ctx):
    got, planned, recs, flog = _job(ctx, list(range(500)))
    assert got == [x * 2 for x in range(500)]
    mix = chip_smoke.check_records("clean", recs, planned, flog, "cpu",
                                   warm=False, dirty_share=0.0)
    assert mix["interpreter_share"] == 0.0
    assert recs[0]["tier"] == "compiled"


def test_injected_trace_failure_fails_the_check_naming_the_stage(
        ctx, monkeypatch):
    """A stage whose trace fails for a reason other than NotCompilable
    still degrades to the interpreter (right answer, exit 0) — but the
    demotion is in failure_log with the exception and the stage's tier
    reads 'interpreter', so the smoke's check fails naming the stage
    instead of passing with a warning."""
    from tuplex_tpu.plan.physical import TransformStage

    def boom(self, *a, **kw):
        raise RuntimeError("injected trace failure")

    monkeypatch.setattr(TransformStage, "build_device_fn", boom)
    got, planned, recs, flog = _job(ctx, list(range(300)))
    assert got == [x * 2 for x in range(300)]       # degraded, not dead
    assert recs[0]["tier"] == "interpreter"
    assert len(flog) == 1 and flog[0]["action"] == "interpreter"
    assert flog[0]["phase"] == "build"
    assert "RuntimeError: injected trace failure" in flog[0]["error"]
    skey = planned[0].key()[:16]
    assert flog[0]["stage"] == skey
    with pytest.raises(chip_smoke.SmokeFailure) as ei:
        chip_smoke.check_records("zillow/cold", recs, planned, flog, "cpu",
                                 warm=False, dirty_share=0.0)
    assert skey in str(ei.value) and "injected trace failure" in str(ei.value)
    # the tier alone is enough: a check that lost the log still fails
    with pytest.raises(chip_smoke.SmokeFailure, match="'interpreter' tier"):
        chip_smoke.check_records("zillow/cold", recs, planned, [], "cpu",
                                 warm=False, dirty_share=0.0)


def test_injected_device_failure_fails_the_check(ctx):
    """The fault point of test_models::test_failure_log_retry_and_degrade
    (a poisoned collect): retried, then run on the interpreter — and
    refused by the smoke's check."""
    import tuplex_tpu.exec.local as LB

    orig = LB.LocalBackend._collect_partition

    def poisoned(self, stage, part, outs, dispatch_s, **kw):
        if outs is not None:
            raise RuntimeError("injected device failure")
        return orig(self, stage, part, outs, dispatch_s, **kw)

    LB.LocalBackend._collect_partition = poisoned
    try:
        got, planned, recs, flog = _job(ctx, [1, 2, 3])
    finally:
        LB.LocalBackend._collect_partition = orig
    assert got == [2, 4, 6]
    assert [e["action"] for e in flog] == ["retry", "interpreter"]
    with pytest.raises(chip_smoke.SmokeFailure, match="injected device"):
        chip_smoke.check_records("serve/q6", recs, planned, flog, "cpu",
                                 warm=False, dirty_share=0.0)


def test_warm_compile_and_interpreter_share_are_refused():
    ok = {"tier": "compiled", "fast_path_s": 0.5, "rows_seen": 1000,
          "resolve_interpreter_rows": 0, "stage_compiles": 0}
    chip_smoke.check_records("p", [ok], None, [], "cpu", True, 0.0)
    with pytest.raises(chip_smoke.SmokeFailure, match="warm run"):
        chip_smoke.check_records("p", [dict(ok, stage_compiles=1)], None,
                                 [], "cpu", True, 0.0)
    with pytest.raises(chip_smoke.SmokeFailure, match="interpreter tier"):
        chip_smoke.check_records(
            "p", [dict(ok, resolve_interpreter_rows=100)], None, [], "cpu",
            False, 0.06)
    with pytest.raises(chip_smoke.SmokeFailure, match="never ran"):
        chip_smoke.check_records("p", [dict(ok, fast_path_s=0.0)], None,
                                 [], "cpu", False, 0.0)
    with pytest.raises(chip_smoke.SmokeFailure, match="cpu-compiled"):
        chip_smoke.check_records("p", [dict(ok, tier="cpu-compiled")],
                                 None, [], "cpu", False, 0.0)


_CACHE_PROBE = ("import jax, tuplex_tpu, json; "
                "from tuplex_tpu.runtime import jaxcfg; "
                "print(json.dumps([jax.config.jax_compilation_cache_dir, "
                "jaxcfg.aot_cache_dir()]))")
_CACHE_VARS = ("JAX_COMPILATION_CACHE_DIR", "TUPLEX_AOT_CACHE",
               "TUPLEX_COMPILE_CACHE")


def test_jax_cache_dir_from_the_environment_is_left_alone(tmp_path):
    want = str(tmp_path / "x")
    r = _run(["-c", _CACHE_PROBE],
             {"JAX_COMPILATION_CACHE_DIR": want, "HOME": str(tmp_path)},
             drop=_CACHE_VARS)
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(r.stdout.splitlines()[-1])[0] == want


def test_caches_default_to_fixed_dirs_inside_the_checkout(tmp_path):
    """Unset, both stores are fixed siblings under the checkout —
    not under ~ (HOME points at an empty dir here and must stay empty),
    and the same in every process (the path is part of jax's cache key)."""
    home = tmp_path / "home"
    home.mkdir()
    outs = []
    for _ in range(2):
        r = _run(["-c", _CACHE_PROBE], {"HOME": str(home)},
                 drop=_CACHE_VARS)
        assert r.returncode == 0, r.stderr[-2000:]
        outs.append(json.loads(r.stdout.splitlines()[-1]))
    root = os.path.join(REPO, ".tuplex_cache")
    assert outs[0] == outs[1] == [os.path.join(root, "xla"),
                                  os.path.join(root, "aot")]
    assert list(home.iterdir()) == []
    # and git ignores it
    with open(os.path.join(REPO, ".gitignore")) as fp:
        assert ".tuplex_cache/" in fp.read().split()


def test_aot_dir_variable_still_works(tmp_path):
    r = _run(["-c", _CACHE_PROBE],
             {"TUPLEX_AOT_CACHE": str(tmp_path / "a"),
              "TUPLEX_COMPILE_CACHE": str(tmp_path / "gone")},
             drop=_CACHE_VARS)
    assert r.returncode == 0, r.stderr[-2000:]
    xla, aot = json.loads(r.stdout.splitlines()[-1])
    assert aot == str(tmp_path / "a")
    assert not (tmp_path / "gone").exists()     # that variable is gone
    assert xla == os.path.join(REPO, ".tuplex_cache", "xla")
