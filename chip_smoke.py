#!/usr/bin/env python3
"""Chip smoke: Zillow, an aggregate and a join through `Context`, on the TPU.

The quickest proof that the system still starts on the chip. One process
holds the chip and drives the normal entry points only
(``tuplex_tpu.Context``, ``ctx.csv``, ``models/*``, ``collect()``,
``Context.submit``) over data generated from ``--seed``; every result is
compared with the model's own CPython reference, and after every phase the
PROGRAM'S OWN RECORDS are read: a run that exits 0 with the chip idle (a
stage demoted to the host CPU or the interpreter, a compile in the warm run,
a logged failure) fails here, naming the stage.

    python chip_smoke.py                 # one chip: zillow, agg, join, serve
    python chip_smoke.py --chips 4       # the mesh path only (4 chips)
    JAX_PLATFORMS=cpu TUPLEX_COMPILE_ISOLATION=thread \\
        python chip_smoke.py --rehearse --rows 20000    # never prints ok

It refuses to start unless ``jax.devices()[0].platform == "tpu"``: there is no
CPU arm. ``--rehearse`` relaxes only that refusal (control-flow rehearsal on
XLA:CPU); a rehearsal never prints the ``"ok": true`` line and exits 3.

Earlier lines are one JSON object per phase (rows, cold/warm seconds, compile
seconds and count, split decision, H2D/D2H bytes, tier mix, peak device
memory, ...). No rate is computed against a peak. The LAST line, only on
success, is ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": N}}``.

Helper processes (data generation and the CPython references) are pinned
to ``JAX_PLATFORMS=cpu`` before they import anything, so they can never
touch the chip; with ``--chips 4`` the orchestrating parent never imports
jax at all and runs the mesh jobs in two successive child processes (the
second must LOAD the first one's stored mesh executables, not compile).
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import shutil
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROWS = 2_000_000
ZILLOW_DIRTY_SHARE = 0.06   # models/zillow.gen_row: 4% facts + 2% postal
# tuplex.tpu.compileDeadlineS for the smoke's Contexts. The program's default
# is 300 s; the packed Zillow stage compiles on the v5e in 143.6 s (my chip
# run, PR 24; 418 s before runtime/packing's unpack was repaired, which
# tripped the default: the stage restarted on the host-CPU tier and this
# script failed, as it should). The host's cores are shared, so the smoke
# gives the compile room and checks everything BEHIND it; the setup line
# prints the value.
COMPILE_DEADLINE_S = 900
REL_TOL = 1e-6              # the repo's own tolerance (tests/test_models)


class SmokeFailure(Exception):
    pass


def say(**rec) -> None:
    print(json.dumps(rec, default=str), flush=True)


# ---------------------------------------------------------------------------
# helper-process work: data + CPython references (never on the chip)
# ---------------------------------------------------------------------------

def _helper_init() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, HERE)


def _gen_zillow(path: str, rows: int, seed: int) -> str:
    from tuplex_tpu.models import zillow

    return zillow.generate_csv(path, rows, seed=seed)


def _gen_lineitem(path: str, rows: int, seed: int) -> str:
    from tuplex_tpu.models import tpch

    return tpch.generate_csv(path, rows, seed=seed)


def _gen_q19(part: str, li: str, n_parts: int, rows: int, seed: int) -> str:
    from tuplex_tpu.models import tpch

    tpch.generate_q19_csvs(part, li, n_parts, rows, seed=seed)
    return li


def _ref_zillow(path: str) -> list:
    from tuplex_tpu.models import zillow

    return zillow.run_reference_python(path)


def _ref_q1(path: str) -> dict:
    from tuplex_tpu.models import tpch

    return tpch.run_reference_q1(path)


def _ref_q6(path: str) -> float:
    from tuplex_tpu.models import tpch

    return tpch.run_reference_q6(path)


def _ref_q19(part: str, li: str) -> float:
    from tuplex_tpu.models import tpch

    return tpch.run_reference_q19(part, li)


def helper_pool(workers: int):
    """A small process pool for generation and references: spawned (never
    forked from a process that may hold the chip) and pinned to the CPU
    platform before any import."""
    import multiprocessing as mp
    from concurrent.futures import ProcessPoolExecutor

    return ProcessPoolExecutor(
        max_workers=workers, mp_context=mp.get_context("spawn"),
        initializer=_helper_init)


def prepare_data(helpers, work: str, rows: int, seed: int,
                 want: tuple) -> dict:
    """Kick off generation, then the references behind it; returns futures.
    `want` names the datasets needed ("zillow", "lineitem", "q19")."""
    fut: dict = {}
    if "zillow" in want:
        z = os.path.join(work, "zillow.csv")
        fut["zillow_csv"] = helpers.submit(_gen_zillow, z, rows, seed)
    if "lineitem" in want:
        li = os.path.join(work, "lineitem.csv")
        fut["lineitem_csv"] = helpers.submit(_gen_lineitem, li, rows,
                                             seed + 1)
    if "q19" in want:
        pq = os.path.join(work, "q19_part.csv")
        lq = os.path.join(work, "q19_lineitem.csv")
        fut["q19_paths"] = (pq, lq)
        fut["q19_csv"] = helpers.submit(_gen_q19, pq, lq,
                                        max(200, rows // 50), rows,
                                        seed + 2)
    return fut


# ---------------------------------------------------------------------------
# comparisons (the repo's own means: exact rows for zillow, 1e-6 for sums)
# ---------------------------------------------------------------------------

def close(a: float, b: float) -> bool:
    return abs(a - b) <= REL_TOL * max(1.0, abs(b))


def compare_q1(out: list, want: dict) -> None:
    got = {(r[0], r[1]): r[2:] for r in out}
    if set(got) != set(want):
        raise SmokeFailure(f"q1 groups differ: {sorted(got)} vs "
                           f"{sorted(want)}")
    for k, w in want.items():
        if not all(close(g, v) for g, v in zip(got[k], w)):
            raise SmokeFailure(f"q1 group {k}: got {got[k]} want {w}")


def compare_scalar(name: str, got: float, want: float) -> None:
    if not close(got, want):
        raise SmokeFailure(f"{name}: got {got!r} want {want!r}")


def compare_rows(name: str, got: list, want: list) -> None:
    if got != want:
        n = next((i for i, (g, w) in enumerate(zip(got, want)) if g != w),
                 min(len(got), len(want)))
        raise SmokeFailure(f"{name}: {len(got)} rows vs {len(want)} in the "
                           f"reference; first difference at row {n}")


# ---------------------------------------------------------------------------
# the program's own records
# ---------------------------------------------------------------------------

def stage_name(i: int, planned) -> str:
    st = planned[i] if planned is not None and i < len(planned) else None
    key = ""
    if st is not None and hasattr(st, "key"):
        try:
            key = st.key()[:12]
        except Exception:
            key = ""
    return f"stage {i} ({type(st).__name__ if st is not None else '?'} {key})"


def check_records(label: str, recs: list, planned, failure_log: list,
                  platform: str, warm: bool, dirty_share: float) -> dict:
    """Fail, naming the stage, on any sign that work left the chip."""
    from tuplex_tpu.exec import compilequeue as CQ

    if failure_log:
        e = failure_log[0]
        raise SmokeFailure(
            f"{label}: failure_log is not empty — stage {e.get('stage')} "
            f"{e.get('action')}: {e.get('error')} "
            f"({len(failure_log)} entries)")
    aligned = planned if planned is not None and len(planned) == len(recs) \
        else None
    for i, st in enumerate(planned or ()):
        if getattr(st, "route_reason", ""):
            raise SmokeFailure(f"{label}: {stage_name(i, planned)} was "
                               f"routed off the device at plan time: "
                               f"{st.route_reason}")
    fast = 0.0
    seen = interp = compiles = 0
    for i, m in enumerate(recs):
        tier = m.get("tier")
        if tier is not None and tier != "compiled":
            raise SmokeFailure(f"{label}: {stage_name(i, aligned)} ran on "
                               f"the '{tier}' tier, not the device-compiled "
                               f"one")
        if m.get("tier_restarts"):
            raise SmokeFailure(f"{label}: {stage_name(i, aligned)} "
                               f"restarted {m['tier_restarts']}x down the "
                               f"tier ladder")
        fast += float(m.get("fast_path_s", 0.0))
        seen += int(m.get("rows_seen", 0))
        interp += int(m.get("resolve_interpreter_rows", 0))
        compiles += int(m.get("stage_compiles", 0))
    if fast <= 0.0:
        raise SmokeFailure(f"{label}: fastPathWallTime() == 0 — the "
                           f"compiled path never ran")
    if warm and compiles:
        raise SmokeFailure(f"{label}: {compiles} stage compile(s) inside "
                           f"the warm run")
    share = interp / seen if seen else 0.0
    if share > dirty_share + 0.005:
        raise SmokeFailure(
            f"{label}: {interp} of {seen} rows ({share:.3%}) resolved on "
            f"the interpreter tier; the generator's dirty share is "
            f"{dirty_share:.1%}")
    for fp, ex in CQ.executable_devices().items():
        # host-pinned executables ("/cpupin": the small-batch host resolve
        # policy) are on the host CPU by design; a STAGE compiled there is
        # caught above (the 'cpu-compiled' tier)
        if "/cpupin" not in ex["salt"] and \
                any(p != platform for p, _ in ex["devices"]):
            raise SmokeFailure(f"{label}: executable {fp[:12]} was built "
                               f"for {ex['devices']}, not for {platform}")
    if CQ.STATS["subprocess_compiles"]:
        raise SmokeFailure(f"{label}: a compile forked a child process "
                           f"({CQ.STATS['subprocess_compiles']}) from the "
                           f"process that holds the chip")
    return {"rows_seen": seen, "interpreter_share": share,
            "resolve_rows": {
                "exact_exit": sum(int(m.get("resolve_exact_rows", 0))
                                  for m in recs),
                "general": sum(int(m.get("resolve_general_rows", 0))
                               for m in recs),
                "interpreter": interp}}


def run_twice(ctx, label: str, build, compare, platform: str,
              dirty_share: float, rows: int) -> tuple:
    """cold + warm collect() of one pipeline, checked after each run;
    returns (result of the warm run, phase record)."""
    from tuplex_tpu.exec import compilequeue as CQ
    from tuplex_tpu.plan.physical import plan_stages
    from tuplex_tpu.runtime import xferstats

    rec: dict = {"phase": label, "rows": rows}
    got = None
    for run in ("cold", "warm"):
        ds = build()
        planned = plan_stages(ds._op, ctx.options_store)
        n0 = len(ctx.metrics.stages)
        fl0 = len(ctx.backend.failure_log)
        cq0 = CQ.snapshot()
        x0 = xferstats.snapshot()
        t0 = time.perf_counter()
        got = ds.collect()
        rec[f"{run}_s"] = time.perf_counter() - t0
        recs = ctx.metrics.stages[n0:]
        cqd = CQ.delta(cq0)
        xd = xferstats.delta(x0)
        compare(got)
        mix = check_records(f"{label}/{run}", recs, planned,
                            ctx.backend.failure_log[fl0:], platform,
                            warm=(run == "warm"), dirty_share=dirty_share)
        if run == "warm" and cqd["stage_compiles"]:
            raise SmokeFailure(f"{label}/warm: {cqd['stage_compiles']} "
                               f"XLA compile(s) inside the warm run")
        rec[f"{run}_compile_s"] = cqd["compile_s"]
        rec[f"{run}_compiles"] = cqd["stage_compiles"]
        rec[f"{run}_aot_hits"] = cqd["aot_hits"]
        rec[f"{run}_h2d_bytes"] = xd["h2d_bytes"]
        rec[f"{run}_d2h_bytes"] = xd["d2h_bytes"]
        if run == "warm":
            rec["stages"] = [type(s).__name__ for s in planned]
            rec["split"] = [
                {"n_ops": d.n_ops, "k": d.k, "over_budget": d.over_budget,
                 "reason": d.reason}
                for d in (getattr(s, "split_decision", None)
                          for s in planned) if d is not None]
            rec["tiers"] = [m.get("tier") for m in recs]
            rec["fast_path_s"] = sum(float(m.get("fast_path_s", 0.0))
                                     for m in recs)
            rec["device_s"] = sum(float(m.get("device_s", 0.0))
                                  for m in recs)
            rec["hbm_peak_analysis"] = max(
                (int(m.get("hbm_peak", 0)) for m in recs), default=0)
            rec["rows_out"] = len(got)
            rec.update(mix)
    rec["peak_device_bytes"] = peak_device_bytes()
    say(**rec)
    return got, rec


def peak_device_bytes():
    import jax

    try:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.local_devices())
    except Exception:
        return None


def setup_record(dev, n_devices: int, compile_deadline: float) -> dict:
    import jax

    import tuplex_tpu.native as native
    from tuplex_tpu.runtime import jaxcfg

    return {"phase": "setup",
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": n_devices},
            "jax": jax.__version__,
            "compile_deadline_s": compile_deadline,
            "native": "built" if native.get() is not None
            else "python fallback",
            "xla_cache_dir": jax.config.jax_compilation_cache_dir,
            "aot_cache_dir": jaxcfg.aot_cache_dir()}


def require_device(rehearse: bool, chips: int):
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" and not rehearse:
        print(f"chip_smoke: jax found no TPU (devices: {devs[:4]}); this "
              f"script has no CPU arm", file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"chip_smoke: --chips {chips} needs {chips} devices, jax "
              f"reports {len(devs)}", file=sys.stderr)
        sys.exit(2)
    return dev, len(devs)


# ---------------------------------------------------------------------------
# one chip: zillow, agg, join, serve
# ---------------------------------------------------------------------------

def one_chip(args) -> dict:
    sys.path.insert(0, HERE)
    dev, n_dev = require_device(args.rehearse, 1)
    import tuplex_tpu
    from tuplex_tpu.models import tpch, zillow

    platform = dev.platform
    say(**setup_record(dev, n_dev, args.compile_deadline))
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    helpers = helper_pool(6)
    try:
        t0 = time.perf_counter()
        fut = prepare_data(helpers, work, args.rows, args.seed,
                           ("zillow", "lineitem", "q19"))
        zpath = fut["zillow_csv"].result()
        ref_z = helpers.submit(_ref_zillow, zpath)
        lipath = fut["lineitem_csv"].result()
        ref_q1 = helpers.submit(_ref_q1, lipath)
        ref_q6 = helpers.submit(_ref_q6, lipath)
        say(phase="data", zillow_bytes=os.path.getsize(zpath),
            lineitem_bytes=os.path.getsize(lipath),
            generate_s=time.perf_counter() - t0)

        ctx = tuplex_tpu.Context(
            {"tuplex.tpu.compileDeadlineS": args.compile_deadline})

        # -- zillow ---------------------------------------------------------
        run_twice(
            ctx, "zillow", lambda: zillow.build_pipeline(ctx.csv(zpath)),
            lambda got: compare_rows("zillow", got, ref_z.result()),
            platform, ZILLOW_DIRTY_SHARE, args.rows)

        # -- agg: TPC-H Q1 and Q6 -------------------------------------------
        got_q1, _ = run_twice(
            ctx, "agg/q1", lambda: tpch.q1(ctx.csv(lipath)),
            lambda got: compare_q1(got, ref_q1.result()),
            platform, 0.0, args.rows)
        got_q6, _ = run_twice(
            ctx, "agg/q6", lambda: tpch.q6(ctx.csv(lipath)),
            lambda got: compare_scalar("q6", got[0], ref_q6.result()),
            platform, 0.0, args.rows)

        # -- join: TPC-H Q19 ------------------------------------------------
        fut["q19_csv"].result()
        pq, lq = fut["q19_paths"]
        ref_q19 = helpers.submit(_ref_q19, pq, lq)
        run_twice(
            ctx, "join/q19", lambda: tpch.q19(ctx, pq, lq),
            lambda got: compare_scalar("q19", got[0], ref_q19.result()),
            platform, 0.0, args.rows)

        # -- serve: three jobs, two tenants ---------------------------------
        serve_phase(ctx, lipath, got_q1, got_q6, platform)
        ctx.close()
    finally:
        helpers.shutdown(wait=True, cancel_futures=True)
        shutil.rmtree(work, ignore_errors=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": n_dev}


def serve_phase(ctx, lipath, got_q1, got_q6, platform: str) -> None:
    """Three jobs from two tenants through Context.submit, results equal
    to the local ones. The jobs are the TPC-H ones: a serve job rebuilds
    its stages from the shipped spec, the rebuilt jaxpr differs by a few
    characters from the local one, so nothing dedups and every job
    compiles again (4 compiles, 84.6 s, 0 dedup hits on the v5e; my chip
    run, PR 24) — Zillow's 143.6 s compile a second time buys no further
    coverage."""
    from tuplex_tpu.exec import compilequeue as CQ
    from tuplex_tpu.models import tpch

    cq0 = CQ.snapshot()
    t0 = time.perf_counter()
    ds_a = tpch.q6(ctx.csv(lipath))
    ds_b = tpch.q1(ctx.csv(lipath))
    ds_c = tpch.q6(ctx.csv(lipath))
    want_q1 = {(x[0], x[1]): x[2:] for x in got_q1}
    jobs = [
        ("q6", ctx.submit(ds_a, name="q6", tenant="alice"),
         lambda r: compare_scalar("serve/q6", r[0], got_q6[0])),
        ("q1", ctx.submit(ds_b, name="q1", tenant="bob"),
         lambda r: compare_q1(r, want_q1)),
        ("q6-again", ctx.submit(ds_c, name="q6-again", tenant="bob"),
         lambda r: compare_scalar("serve/q6-again", r[0], got_q6[0])),
    ]
    rec = {"phase": "serve", "jobs": []}
    for name, h, compare in jobs:
        rows = h.result(timeout=900)
        if h.error:
            raise SmokeFailure(f"serve/{name}: {h.error}")
        if h.attempts():
            raise SmokeFailure(f"serve/{name}: needed retries: "
                               f"{h.attempts()}")
        compare(rows)
        runner = getattr(h._rec, "runner", None)
        flog = list(runner.backend.failure_log) if runner is not None \
            else []
        check_records(f"serve/{name}", h.metrics.stages, None, flog,
                      platform, warm=False, dirty_share=0.0)
        rec["jobs"].append({"job": name, "tenant": h.tenant,
                            "rows_out": len(rows),
                            "tiers": [m.get("tier")
                                      for m in h.metrics.stages]})
    cqd = CQ.delta(cq0)
    rec["seconds"] = time.perf_counter() - t0
    rec["compiles"] = cqd["stage_compiles"]
    rec["compile_s"] = cqd["compile_s"]
    rec["dedup_hits"] = cqd["dedup_hits"]
    rec["peak_device_bytes"] = peak_device_bytes()
    say(**rec)


# ---------------------------------------------------------------------------
# four chips: zillow + Q1 on the mesh backend, twice, in two processes
# ---------------------------------------------------------------------------

def mesh_child(args) -> None:
    """One process that holds all four chips: zillow and Q1 through
    Context({"tuplex.backend": "multihost"}), each compared with its
    reference; every device must hold a shard of the staged batch. The
    SECOND such process must load the first one's stored mesh executables
    (AOT hits, zero stage compiles)."""
    sys.path.insert(0, HERE)
    dev, n_dev = require_device(args.rehearse, 4)
    import tuplex_tpu
    from tuplex_tpu.exec import compilequeue as CQ
    from tuplex_tpu.models import tpch, zillow

    second = args.mesh_child == "second"
    work = args.work
    with open(os.path.join(work, "refs.pkl"), "rb") as fp:
        refs = pickle.load(fp)
    say(**setup_record(dev, n_dev, args.compile_deadline))
    ctx = tuplex_tpu.Context({
        "tuplex.backend": "multihost", "tuplex.tpu.meshShape": "4",
        "tuplex.tpu.compileDeadlineS": args.compile_deadline})
    be = ctx.backend
    assert be.n_devices == 4, be.n_devices
    mesh_ids = sorted(d.id for d in be.mesh.devices.flat)
    zpath, lipath = refs["zillow_csv"], refs["lineitem_csv"]
    cq0 = CQ.snapshot()
    for label, build, compare, dirty in (
            ("mesh/zillow",
             lambda: zillow.build_pipeline(ctx.csv(zpath)),
             lambda got: compare_rows("mesh/zillow", got, refs["zillow"]),
             ZILLOW_DIRTY_SHARE),
            ("mesh/q1", lambda: tpch.q1(ctx.csv(lipath)),
             lambda got: compare_q1(got, refs["q1"]), 0.0)):
        be.shard_layout = {}
        _, rec = run_twice(ctx, label, build, compare, dev.platform, dirty,
                           args.rows)
        lay = be.shard_layout
        for side in ("input", "output"):
            ids = sorted(i for i, _ in lay.get(side, ()))
            if ids != mesh_ids:
                raise SmokeFailure(
                    f"{label}: the staged {side} lives on devices {ids}, "
                    f"not on all of {mesh_ids}")
            shapes = {s for _, s in lay[side]}
            if len(shapes) != 1:
                raise SmokeFailure(f"{label}: uneven {side} shards: "
                                   f"{lay[side]}")
        say(phase=label + "/shards", input=lay["input"],
            output=lay["output"])
    cqd = CQ.delta(cq0)
    say(phase="mesh/store", process="second" if second else "first",
        stage_compiles=cqd["stage_compiles"], aot_hits=cqd["aot_hits"],
        aot_misses=cqd["aot_misses"], aot_errors=cqd["aot_errors"],
        compile_s=cqd["compile_s"])
    if second:
        if cqd["stage_compiles"] or cqd["aot_errors"] or not cqd["aot_hits"]:
            raise SmokeFailure(
                f"second process did not run off the stored mesh "
                f"executables: {cqd['stage_compiles']} compiles, "
                f"{cqd['aot_hits']} AOT hits, {cqd['aot_errors']} AOT "
                f"errors")
    elif not cqd["stage_compiles"]:
        raise SmokeFailure("first process compiled nothing: the AOT store "
                           "was not empty, so the second process proves "
                           "nothing")
    ctx.close()
    say(phase="mesh/done", device={"platform": dev.platform,
                                   "kind": dev.device_kind, "count": n_dev})


def four_chips(args) -> dict:
    """Orchestrator: never imports jax (a parent that touched it would hold
    the chips its children need)."""
    work = tempfile.mkdtemp(prefix="chip_smoke4_")
    helpers = helper_pool(4)
    device = None
    try:
        t0 = time.perf_counter()
        fut = prepare_data(helpers, work, args.rows, args.seed,
                           ("zillow", "lineitem"))
        zpath = fut["zillow_csv"].result()
        ref_z = helpers.submit(_ref_zillow, zpath)
        lipath = fut["lineitem_csv"].result()
        ref_q1 = helpers.submit(_ref_q1, lipath)
        refs = {"zillow_csv": zpath, "lineitem_csv": lipath,
                "zillow": ref_z.result(), "q1": ref_q1.result()}
        with open(os.path.join(work, "refs.pkl"), "wb") as fp:
            pickle.dump(refs, fp)
        say(phase="data", generate_and_reference_s=time.perf_counter() - t0)
        # a store of its own, fresh for this run: the first child must
        # compile, the second must load exactly what the first stored
        env = dict(os.environ)
        env["TUPLEX_AOT_CACHE"] = os.path.join(work, "aot")
        for which in ("first", "second"):
            cmd = [sys.executable, os.path.abspath(__file__), "--chips", "4",
                   "--rows", str(args.rows), "--seed", str(args.seed),
                   "--compile-deadline", str(args.compile_deadline),
                   "--mesh-child", which, "--work", work]
            if args.rehearse:
                cmd.append("--rehearse")
            p = subprocess.run(cmd, env=env, stdout=subprocess.PIPE,
                               text=True, timeout=args.child_timeout)
            sys.stdout.write(p.stdout)
            sys.stdout.flush()
            if p.returncode != 0:
                raise SmokeFailure(f"mesh child '{which}' exited "
                                   f"{p.returncode}")
            for line in p.stdout.splitlines():
                if line.startswith("{") and '"mesh/done"' in line:
                    device = json.loads(line)["device"]
        if device is None:
            raise SmokeFailure("mesh children reported no device")
    finally:
        helpers.shutdown(wait=True, cancel_futures=True)
        shutil.rmtree(work, ignore_errors=True)
    return device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260930)
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="relax ONLY the platform refusal; never prints "
                         "the ok line, exits 3")
    ap.add_argument("--compile-deadline", type=float,
                    default=COMPILE_DEADLINE_S,
                    help="tuplex.tpu.compileDeadlineS of the smoke's "
                         "Contexts (program default: 300)")
    ap.add_argument("--child-timeout", type=int, default=3000)
    ap.add_argument("--mesh-child", choices=("first", "second"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--work", help=argparse.SUPPRESS)
    args = ap.parse_args()
    try:
        if args.mesh_child:
            mesh_child(args)
            return 0
        device = four_chips(args) if args.chips == 4 else one_chip(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr)
        return 1
    if args.rehearse:
        say(ok=False, rehearsal=True, device=device)
        return 3
    if device["platform"] != "tpu" or device["count"] != args.chips:
        print(f"chip_smoke: ran on {device}, wanted {args.chips} TPU "
              f"chip(s)", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
