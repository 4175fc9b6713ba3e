#!/usr/bin/env python3
"""Runs one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's tables are made from `--seed` in helper processes that never
touch the chip; this process holds the chip(s) and drives whole jobs through
`tuplex_tpu.Context` ... `collect()`: one first job (timed from the creation
of the `Context`), then at once a closed loop of jobs for `--seconds`. Once
the window has closed the helpers compute the plain reference's answer over
the same rows, and the first job's answer and the window's last are compared
with it. The last line of standard output is the result.
Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result; `--rehearse` relaxes only that, and a rehearsal reports no
device metric.
"""

from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import shutil
import sys
import threading
import time

T_START = time.perf_counter()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
MARKER = "bench:marker"
DEVICE_SOURCES = ("device_trace",)


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run without a TPU; reports no device metric")
    ap.add_argument("--rows", type=int, default=0,
                    help="rehearsals and tests only: rows of the fact table")
    ap.add_argument("--control", action="store_true",
                    help="the control of `correct`: the reference, with one "
                         "guarantee broken, stands in for the program's "
                         "first answer; needs no chip")
    return ap.parse_args(argv)


def require_devices(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not rehearse:
        print(f"bench: jax found no TPU (devices: {devs[:4]}); this "
              f"benchmark measures on the chip only", file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chip(s), jax reports "
              f"{len(devs)}", file=sys.stderr)
        sys.exit(2)
    return devs


def spans_since(tracing, t_us: float) -> list:
    """The program's closed spans that started at or after `t_us`; instant
    events carry no duration and are left out."""
    return [e for e in tracing.events_since(t_us)
            if e.get("dur") is not None]


def memory_peak_bytes():
    import jax

    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.local_devices()]
    return max(peaks) if peaks and max(peaks) > 0 else None


def traced_job(runner, trace_dir: str) -> tuple:
    """One job inside a `jax.profiler` trace. Returns the job's record and
    the marker's time on the host clock (perf_counter seconds)."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # the Python tracer floods the trace
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        marker_s = time.perf_counter()
        with jax.profiler.TraceAnnotation(MARKER):
            pass
        rec = runner.job()
    finally:
        jax.profiler.stop_trace()
    return rec, marker_s


def window(runner, seconds: float, trace_dir) -> dict:
    """Whole jobs back to back until `seconds` have passed; the job in
    flight then is finished and counted. With `trace_dir`, the first job
    runs inside a profiler trace."""
    jobs: list = []
    marker_s = None
    last_out = None
    t0 = time.perf_counter()
    while True:
        if trace_dir and not jobs:
            rec, marker_s = traced_job(runner, trace_dir)
        else:
            rec = runner.job()
        out = rec.pop("out")
        if rec["fault"] is None:
            last_out = out
        del out
        jobs.append(rec)
        if time.perf_counter() - t0 >= seconds:
            break
    good = [j for j in jobs if j["fault"] is None]
    end = max((j["t0"] + j["seconds"] for j in good), default=t0)
    return {"t0": t0, "seconds": end - t0, "jobs": jobs, "good": len(good),
            "last_out": last_out, "marker_s": marker_s}


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, BENCH_DIR)
    sys.path.insert(0, ROOT)
    # jax's persistent compilation cache at a fixed path inside the
    # checkout, beside the program's own stores (`.tuplex_cache/aot`), and
    # never a cache the machine brings along: an executable that jax loads
    # from its cache and the program then stores again loads in the next
    # process but cannot run (PERF.md, Open questions), so the two stores
    # have to fill together, from the same compiles. The tests move both
    # together (`TUPLEX_AOT_CACHE` and jax's), and are left alone.
    if "TUPLEX_AOT_CACHE" not in os.environ:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            ROOT, ".tuplex_cache", "xla")
    from harness import datagen, spec

    try:
        cell = spec.Cell(args.workload)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if args.rows:
        if not args.rehearse:
            print("bench: --rows is for rehearsals", file=sys.stderr)
            return 2
        cell.scale_rows(args.rows)
    work = os.path.join(BENCH_DIR, ".cache", "work", cell.name)
    helpers = datagen.pool(BENCH_DIR)
    try:
        if args.control:
            return control(cell, helpers, args.seed, work)
        return measure(args, cell, helpers, work)
    finally:
        helpers.shutdown(wait=True, cancel_futures=True)
        shutil.rmtree(work, ignore_errors=True)


def reach_chip_while_generating(args, cell, helpers, work: str) -> tuple:
    """Generation runs in a thread of its own (the helpers do the work)
    while this process imports jax and reaches the chip. Returns the inputs
    and the device as JAX reports it."""
    from harness import datagen

    box: dict = {}

    def _gen():
        try:
            box["inp"] = datagen.generate(helpers, cell, args.seed, work)
        except BaseException as e:      # re-raised in the main thread
            box["err"] = e

    th = threading.Thread(target=_gen, name="bench-generate")
    th.start()
    try:
        devs = require_devices(cell.chips, args.rehearse)
    finally:
        th.join()
    if "err" in box:
        raise box["err"]
    return box["inp"], {"platform": devs[0].platform,
                        "kind": devs[0].device_kind, "count": len(devs)}


def measure(args, cell, helpers, work: str) -> int:
    from harness import arith, datagen, jobs

    inp, device = reach_chip_while_generating(args, cell, helpers, work)
    rehearsal = device["platform"] != "tpu"
    log(f"device {device}; inputs {inp.rows} {inp.bytes}")
    import tuplex_tpu  # noqa: F401  (the system under test)
    from tuplex_tpu.exec import compilequeue as CQ
    from tuplex_tpu.runtime import tracing, xferstats

    if args.trace:
        tracing.enable(True)
        tracing.clear()

    def spans(t_us: float) -> list:
        return spans_since(tracing, t_us) if args.trace else []

    # ---- the first job: Context creation to the end of collect() ----
    cq0, x0, span0 = CQ.snapshot(), xferstats.snapshot(), tracing.now_us()
    t_ctx = time.perf_counter()
    runner = jobs.Runner(cell, inp.paths, device["platform"])
    try:
        first = runner.job()
        first_job_s = time.perf_counter() - t_ctx
        first_out = first.pop("out")
        first.update(seconds=first_job_s, cq=CQ.delta(cq0),
                     xfer=xferstats.delta(x0), spans=spans(span0))
        log(f"first job {first_job_s:.2f}s compile plane "
            f"{ {k: v for k, v in first['cq'].items() if v} } "
            f"fault {first['fault']}")
        planned, plan_ms = runner.plan()
        if first["fault"] is None:
            try:
                jobs.check_plan(planned)
            except jobs.JobFault as e:
                first["fault"] = str(e)
        gc.collect()
        setup_s = time.perf_counter() - T_START
        log(f"set-up {setup_s:.2f}s")

        # ---- the window ----
        trace_dir = os.path.join(BENCH_DIR, ".cache", "trace", cell.name) \
            if args.trace and not rehearsal else None
        cq0, x0, span0 = CQ.snapshot(), xferstats.snapshot(), tracing.now_us()
        n_stage0 = len(runner.ctx.metrics.stages)
        win = window(runner, args.seconds, trace_dir)
        win.update(cq=CQ.delta(cq0), xfer=xferstats.delta(x0),
                   spans=spans(span0),
                   stages=list(runner.ctx.metrics.stages[n_stage0:]))
        rows_job = sum(inp.rows[t] for t in cell.job_tables())
        win["rows"] = rows_job * win["good"]
        log(f"window {win['seconds']:.2f}s jobs "
            f"{[round(j['seconds'], 2) for j in win['jobs']]} good "
            f"{win['good']} compile plane "
            f"{ {k: v for k, v in win['cq'].items() if v} }")
        mem_peak = None if rehearsal else memory_peak_bytes()
        shard_layout = getattr(runner.ctx.backend, "shard_layout", None)
    finally:
        runner.close()

    # ---- correct: the first job's answer and the window's last ----
    # The reference runs now, in the helpers that sat idle since the
    # tables were made: the window is closed, the peak is read and the
    # program's state is freed, so its seconds are in no metric.
    t_ref = time.perf_counter()
    datagen.start_reference(helpers, cell, inp)
    want = datagen.merge_reference(cell, datagen.wait_reference(inp))
    log(f"reference {time.perf_counter() - t_ref:.2f}s")
    compared = compare_outputs(
        cell, [("first_job", first_out),
               ("last_window_job", win.pop("last_out"))], want)
    faults = [j["fault"] for j in [first] + win["jobs"] if j["fault"]]
    numbers_ok = all(c["value"] <= c["limit"] for c in compared.values())
    correct = bool(numbers_ok and not faults and win["good"] > 0)
    answer_bytes = cell.pipeline().answer_bytes(want)
    del want, first_out

    # ---- metrics ----
    breakdown = None
    if not args.trace:
        values = {"rows_per_s": arith.rate(win["rows"], win["seconds"])
                  if win["good"] else None,
                  "first_job_s": first_job_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end
                   if values.get(m["name"]) is not None}
    else:
        trace = None
        if win["marker_s"] is not None:
            trace = read_trace(trace_dir, win, tracing)
            shutil.rmtree(trace_dir, ignore_errors=True)
        if trace is not None:
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            breakdown = {"device_ops": trace["device_ops"],
                         "idle_gaps": trace["idle_gaps"]}
        metrics = per_layer_metrics(cell, {
            "cell": {"name": cell.name, "chips": cell.chips, "rows": rows_job,
                     "input_bytes": inp.input_bytes(cell.job_tables()),
                     "answer_bytes": answer_bytes},
            "device": device, "rehearsal": rehearsal, "plan_ms": plan_ms,
            "first_job": first, "window": win,
            "trace": trace, "memory_peak_bytes": mem_peak,
            "shard_layout": shard_layout})
    if mem_peak is not None:
        device["memory_peak_bytes"] = mem_peak

    result = {"correct": correct, "attempted": 1 + len(win["jobs"]),
              "failed": len(faults), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # beyond the contract's keys: what a reader of one run's line wants
    result.update(workload=cell.name, seed=args.seed, rehearsal=rehearsal,
                  faults=faults[:3])
    for f in faults[:3]:
        print(f"bench: job fault: {f}", file=sys.stderr)
    emit(result, compared)
    return 0


def per_layer_metrics(cell, run: dict) -> dict:
    """Each per-layer metric of the cell through its reader; a reader that
    finds nothing is left out, and a rehearsal reads no device metric."""
    from harness import peaks

    run["peaks"] = None if run["rehearsal"] \
        else peaks.peaks(run["device"]["kind"])
    metrics = {}
    for m in cell.per_layer:
        if run["rehearsal"] and m["source"] in DEVICE_SOURCES:
            continue
        value = cell.reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def compare_outputs(cell, outputs: list, want) -> dict:
    """Every number of the cell's comparison, the worst over `outputs`
    [(label, answer)]; an answer that is absent is a number of its own."""
    compared: dict = {}
    absent = sum(1 for _, out in outputs if out is None)
    compared["answers_absent"] = {"value": absent, "limit": 0}
    for label, out in outputs:
        if out is None:
            continue
        for name, value, limit in cell.pipeline().compare(
                out, want, cell.limits):
            prev = compared.get(name)
            if prev is None or not value <= prev["value"]:
                compared[name] = {"value": value, "limit": limit,
                                  "of": label}
    return compared


def emit(result: dict, compared: dict) -> None:
    """The numbers compared, each beside its limit: the last lines of
    standard error, and the last key of the result's line."""
    from harness import arith

    result["compared"] = {
        k: {"value": arith.finite(c["value"]), "limit": c["limit"]}
        for k, c in compared.items()}
    for k, c in compared.items():
        print(f"bench: compared {k} = {c['value']!r} (limit "
              f"{c['limit']!r}) {c.get('of', '')}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def control(cell, helpers, seed: int, work: str) -> int:
    """The control of `correct`: the reference with one guarantee broken
    (the nearest lower precision, or the order) stands in for the program's
    answer at the cell's own size. It touches no device; `correct` has to
    come out false."""
    from harness import datagen

    inp = datagen.generate(helpers, cell, seed, work)
    datagen.start_reference(helpers, cell, inp)
    want = datagen.merge_reference(cell, datagen.wait_reference(inp))
    datagen.start_reference(helpers, cell, inp, control=True)
    got = datagen.merge_reference(cell, datagen.wait_reference(inp),
                                  control=True)
    compared = compare_outputs(cell, [("control", got)], want)
    ok = all(c["value"] <= c["limit"] for c in compared.values())
    emit({"correct": bool(ok), "control": True, "workload": cell.name,
          "seed": seed}, compared)
    return 0


def read_trace(trace_dir: str, win: dict, tracing):
    """Reduce the traced job's `.xplane.pb`; None where there is none."""
    from harness import xplane

    pbs = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                    recursive=True)
    if not pbs:
        return None
    job = win["jobs"][0]
    lo = tracing.to_trace_us(job["t0"])
    return xplane.reduce_trace(
        xplane.load(pbs[0]), MARKER, tracing.to_trace_us(win["marker_s"]),
        (lo, lo + job["seconds"] * 1e6), win["spans"])


if __name__ == "__main__":
    rc = main()
    # The program's compile pool leaves daemon threads (`tpx-compile-*`)
    # that can abort the interpreter's finalization after the result is out
    # (2 of 7 CPU rehearsals ended with SIGABRT). Everything this script
    # started has been stopped and waited for by now.
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
