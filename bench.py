#!/usr/bin/env python3
"""Benchmark driver: Zillow Z1 cleaning pipeline end-to-end.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

value      = input rows/sec through the full framework pipeline (CSV read +
             device decode + 10-op fused UDF stage + dual-mode resolve +
             collect), steady-state (post-compile), best of N runs.
             NOTE: defaults are 100k rows / best-of-2 since round 1 (override
             with BENCH_ROWS/BENCH_RUNS); rows are always reported on stderr
             so runs at different sizes aren't silently compared.
vs_baseline = speedup over the pure-CPython interpreter implementation of the
             SAME pipeline on the same data (the reference's own comparison
             methodology: benchmarks/zillow runs 1 warmup + timed runs).
Output parity with the interpreter implementation is asserted every run.

One process, one backend: the benchmark runs in THIS process on jax's
default backend (the chip where there is one; `JAX_PLATFORMS=cpu` for an
XLA:CPU run), prints the device it ran on, and exits non-zero where the
compiled fast path never ran. There is no second platform to fall back to
and no child process: a chip belongs to one process at a time.
"""

from __future__ import annotations

import json
import os
import sys
import time

N_ROWS = int(os.environ.get("BENCH_ROWS", "100000"))
BASELINE_ROWS = int(os.environ.get("BENCH_BASELINE_ROWS", "40000"))
# n_trials >= 3 so the JSON line carries a best-of-N spread (spread <= 10%
# or the number is machine noise: the interpreter baseline has been seen
# swinging 1.5x across a day on a shared host)
RUNS = int(os.environ.get("BENCH_RUNS", "3"))
# soft wall budget for the secondary suite (seconds from start; 0 = none):
# the primary metric is printed (and flushed) first, so the suite must
# never cost it
SUITE_BUDGET_S = float(os.environ.get("BENCH_SUITE_BUDGET", "0"))


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _vs_llvm(rate: float):
    """Speedup vs the reference LLVM engine's rows/s on the same pipeline
    (scripts/llvm_baseline.py records the denominator — measured where the
    reference engine is installed, else an explicitly-labeled estimate —
    into BASELINE_LLVM.json). (None, "") when no denominator is recorded."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BASELINE_LLVM.json")
    try:
        with open(path) as fp:
            d = json.load(fp)
        base = float(d["zillow_rows_per_sec"])
        if base > 0:
            return round(rate / base, 3), d.get("kind", "unknown")
    except (OSError, KeyError, ValueError, TypeError):
        pass
    return None, ""


def main() -> None:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import tempfile

    import jax

    t_start = time.time()
    t0 = time.perf_counter()
    dev = jax.devices()[0]
    actual = dev.platform
    device = {"platform": actual, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"bench: backend up in {time.perf_counter() - t0:.1f}s -> "
          f"{json.dumps(device)}", file=sys.stderr)

    import tuplex_tpu
    from tuplex_tpu.models import zillow

    cache_dir = os.path.join(tempfile.gettempdir(), "tuplex_tpu_bench")
    os.makedirs(cache_dir, exist_ok=True)
    data = os.path.join(cache_dir, f"zillow_{N_ROWS}.csv")
    if not os.path.exists(data):
        zillow.generate_csv(data, N_ROWS, seed=42)
    base_data = os.path.join(cache_dir, f"zillow_{BASELINE_ROWS}.csv")
    if not os.path.exists(base_data):
        zillow.generate_csv(base_data, BASELINE_ROWS, seed=42)

    # --- framework + pure-python baseline, INTERLEAVED -------------------
    # A shared host drifts minute to minute (the interpreter baseline has
    # swung 105-156k rows/s across a day, moving vs_baseline 0.94-1.22x
    # with no code change). Alternating fw/py samples makes
    # both sides see the same machine state; best-of-N per side.
    from tuplex_tpu.runtime import xferstats

    conf = {}
    spec_env = os.environ.get("BENCH_SPECULATE")
    spec_on = spec_env is not None and spec_env not in ("0", "false")
    if spec_env is not None:
        # A/B flag for the branch-speculation measurement:
        # BENCH_SPECULATE=0 re-runs the same bench with sample-driven
        # dead-branch pruning off so the kernel delta is one env var away
        conf["tuplex.optimizer.speculateBranches"] = spec_on
    ctx = tuplex_tpu.Context(conf)
    got = None
    times = []
    d2h_per_run = []
    h2d_per_run = []
    base_times = []
    stage_slices = []    # (start, end) into ctx.metrics.stages per timed run
    for i in range(RUNS + 1):
        xsnap = xferstats.snapshot()
        n_stages0 = len(ctx.metrics.stages)
        t0 = time.perf_counter()
        ds = zillow.build_pipeline(ctx.csv(data))
        got = ds.collect()
        dt = time.perf_counter() - t0
        if i > 0:  # first run includes XLA compile
            times.append(dt)
            stage_slices.append((n_stages0, len(ctx.metrics.stages)))
            xd = xferstats.delta(xsnap)
            d2h_per_run.append(xd["d2h_bytes"])
            h2d_per_run.append(xd["h2d_bytes"])
        base_times.append(_timed(
            lambda: zillow.run_reference_python(base_data)))
    best = min(times)
    rate = N_ROWS / best
    base_rate = BASELINE_ROWS / min(base_times)
    # boundary-transfer tax of the steady-state run (runtime/xferstats):
    # this is the number the varlen wire + device-resident handoff shrink
    d2h_bytes = d2h_per_run[times.index(best)] if d2h_per_run else 0
    h2d_bytes = h2d_per_run[times.index(best)] if h2d_per_run else 0
    spread = (max(times) - min(times)) / min(times) if times else 0.0

    # --- correctness gate --------------------------------------------------
    want = zillow.run_reference_python(data)
    ok = got == want
    if not ok:
        print(f"OUTPUT MISMATCH: got {len(got)} rows, want {len(want)}",
              file=sys.stderr)

    # --- latency budget (runtime/critpath) ---------------------------------
    # one extra NON-timed run with tracing on (the timed loop above runs
    # untraced so the ring append never rides the measurement), then sweep
    # the span timeline into the exclusive bucket vector: bench_diff gates
    # the dotted latency_budget.* keys (the interpreter-resolve share and
    # the unattributed remainder must not grow)
    latency_budget = {}
    try:
        from tuplex_tpu.runtime import tracing
        was_on = tracing.enabled()
        tracing.enable(True)
        tracing.clear()
        zillow.build_pipeline(ctx.csv(data)).collect()
        latency_budget = ctx.metrics.latencyBudget()
        tracing.enable(was_on)
        tracing.clear()
    except Exception as e:   # readout is best-effort, never fails the bench
        print(f"latency_budget skipped: {type(e).__name__}: {e}",
              file=sys.stderr)

    fast_s = ctx.metrics.fastPathWallTime()
    vs_llvm, llvm_kind = _vs_llvm(rate)
    # device-plane cost attribution (runtime/devprof) for the BEST timed
    # run's stages: measured device seconds, XLA flops/bytes, peak device
    # memory and the roofline fraction per stage — the numbers the
    # /metrics exposition and the dashboard stage table also show
    lo, hi = stage_slices[times.index(best)]
    stage_costs = {}
    device_s = 0.0
    hbm_peak = 0
    for si, m in enumerate(ctx.metrics.stage_breakdown()[lo:hi]):
        if "device_s" not in m:
            continue
        device_s += m["device_s"]
        hbm_peak = max(hbm_peak, int(m.get("hbm_peak", 0)))
        stage_costs[str(si)] = {
            k: (round(m[k], 6) if isinstance(m[k], float) else m[k])
            for k in ("device_s", "flops", "device_bytes", "hbm_peak",
                      "roofline_frac", "wall_s", "compile_s")
            if k in m}
    result = {
        "metric": "zillow_z1_rows_per_sec",
        "value": round(rate, 1),
        "unit": "rows/s",
        "vs_baseline": round(rate / base_rate, 3),
        # vs the reference LLVM engine's measured-or-estimated rows/s
        # (scripts/llvm_baseline.py -> BASELINE_LLVM.json); null until a
        # denominator is recorded, and the kind says whether it was a real
        # measurement or a labeled estimate
        "vs_llvm": vs_llvm,
        "vs_llvm_kind": llvm_kind,
        "platform": actual,
        "device": device,
        "d2h_bytes": int(d2h_bytes),
        "h2d_bytes": int(h2d_bytes),
        "n_trials": len(times),
        "spread": round(spread, 3),
        # compile pipeline: total stage-executable compile seconds across
        # the whole process (first run pays it, steady-state runs are free;
        # 0.0 with a warm AOT artifact cache) + actual XLA compile count
        "compile_s": round(ctx.metrics.compileTime(), 3),
        "stage_compiles": ctx.metrics.stageCompileCount(),
        # measured device seconds of the best run + the largest stage
        # executable's peak device-memory footprint, with the per-stage
        # breakdown (device_s/flops/device_bytes/hbm_peak/roofline_frac)
        # under dotted keys bench_diff gates directionally
        "device_s": round(device_s, 4),
        "hbm_peak": hbm_peak,
        "stage_costs": stage_costs,
        # plan-time static-analysis cost + how many operators the analyzer
        # routed to the interpreter without ever invoking the emitter
        "analyzer_ms": round(ctx.metrics.analyzerTimeMs(), 3),
        "plan_fallback_ops": ctx.metrics.planFallbackOps(),
        # sample-free specialization: operators typed exactly from the AST
        # and the CPython sample traces that verdict let planning skip
        "analyzer_inferred_ops": ctx.metrics.analyzerInferredOps(),
        "sample_traces_skipped": ctx.metrics.sampleTracesSkipped(),
        # critical-path wall attribution of one traced steady-state run
        # (runtime/critpath): bucket seconds + unattributed_frac under
        # dotted keys bench_diff gates directionally
        "latency_budget": latency_budget,
    }
    if spec_env is not None:
        result["speculate_branches"] = spec_on
    # extra context on stderr (driver only parses stdout JSON line)
    print(json.dumps({
        "rows": N_ROWS, "best_s": round(best, 3),
        "runs_s": [round(t, 3) for t in times],
        "spread": round(spread, 3),
        "d2h_bytes_per_run": [int(b) for b in d2h_per_run],
        "h2d_bytes_per_run": [int(b) for b in h2d_per_run],
        "platform": actual,
        "interp_rows_per_sec": round(base_rate, 1),
        "output_rows": len(got) if got else 0,
        "output_matches_interpreter": ok,
        "fast_path_s": round(fast_s, 3),
        "slow_path_s": round(ctx.metrics.slowPathWallTime(), 3),
        "compile_s": round(ctx.metrics.compileTime(), 3),
    }), file=sys.stderr)
    if fast_s == 0.0:
        # the whole pipeline ran on the interpreter: the number above does
        # not measure the compiled path at all. Never report that silently.
        print("bench: *** FAST PATH NEVER RAN — the run measured the "
              "interpreter fallback, not the framework. ***",
              file=sys.stderr)
        sys.exit(4)  # never report an interpreter number as a device run
    # print the primary result BEFORE the suite: a wedged/slow secondary
    # config must never forfeit an already-computed number
    print(json.dumps(result), flush=True)
    if os.environ.get("BENCH_SUITE", "1") != "0":
        _suite(cache_dir, actual,
               t_start + SUITE_BUDGET_S if SUITE_BUDGET_S > 0 else None)


def _suite(cache_dir: str, platform: str, deadline=None) -> None:
    """Secondary tracked configs (BASELINE.md): flights, logs-regex,
    TPC-H Q1/Q6, NYC 311. One stderr JSON line each — rows/s + speedup over
    the pure-python implementation of the same pipeline. The primary stdout
    metric stays zillow-only; this records breadth."""
    import time

    import tuplex_tpu
    from tuplex_tpu.models import flights, logs, nyc311, tpch

    n = int(os.environ.get("BENCH_SUITE_ROWS", "60000"))

    def prep(name, gen):
        path = os.path.join(cache_dir, name)
        if not os.path.exists(path):
            gen(path)
        return path

    fp = prep(f"perf_{n}.csv", lambda p: flights.generate_perf_csv(p, n))
    cp = prep("carrier.csv", flights.generate_carrier_csv)
    ap = prep("airport.db", flights.generate_airport_db)
    lg = prep(f"logs_{n}.txt", lambda p: logs.generate_log(p, n))
    li = prep(f"lineitem_{n}.csv", lambda p: tpch.generate_csv(p, n))
    nc = prep(f"n311_{n}.csv", lambda p: nyc311.generate_csv(p, n))
    pq = os.path.join(cache_dir, f"q19part_{n}.csv")
    lq = os.path.join(cache_dir, f"q19li_{n}.csv")
    if not (os.path.exists(pq) and os.path.exists(lq)):
        tpch.generate_q19_csvs(pq, lq, max(200, n // 50), n)

    ctx = tuplex_tpu.Context()
    metrics = ctx.metrics
    # cheap configs first: flights' many-stage compile can eat the whole
    # suite budget, and a config that overruns starves every config queued
    # behind it
    configs = [
        ("tpch_q6", lambda: tpch.q6(ctx.csv(li)).collect(),
         lambda: tpch.run_reference_q6(li)),
        ("tpch_q1", lambda: tpch.q1(ctx.csv(li)).collect(),
         lambda: tpch.run_reference_q1(li)),
        ("nyc311", lambda: nyc311.build_pipeline(ctx, nc).collect(),
         lambda: nyc311.run_reference_python(nc)),
        ("logs_regex", lambda: logs.build_pipeline(ctx.text(lg),
                                                   "regex").collect(),
         lambda: logs.run_reference_python(lg, "regex")),
        ("tpch_q19", lambda: tpch.q19(ctx, pq, lq).collect(),
         lambda: tpch.run_reference_q19(pq, lq)),
        ("flights", lambda: flights.build_pipeline(ctx, fp, cp, ap).collect(),
         lambda: flights.run_reference_python(fp, cp, ap)),
    ]
    for name, run, ref in configs:
        if deadline is not None and time.time() > deadline - 30:
            print(json.dumps({"suite": name, "error": "skipped: deadline"}),
                  file=sys.stderr)
            continue
        try:
            run()                              # warm (compile)
            fast0 = metrics.fastPathWallTime()
            t0 = time.perf_counter()
            run()
            fw = time.perf_counter() - t0
            if metrics.fastPathWallTime() <= fast0:
                # compiled path never ran: an interpreter number must not
                # masquerade as framework throughput (same guard as the
                # primary metric)
                print(json.dumps({"suite": name,
                                  "error": "fast path never ran"}),
                      file=sys.stderr)
                continue
            py = min(_timed(ref) for _ in range(2))  # baseline jitter guard
            print(json.dumps({
                "suite": name, "rows": n, "platform": platform,
                "framework_s": round(fw, 3), "python_s": round(py, 3),
                "rows_per_sec": round(n / fw, 1),
                "speedup_vs_python": round(py / fw, 2)}), file=sys.stderr)
        except Exception as e:  # a broken secondary config must not kill
            print(json.dumps({"suite": name,                # the bench
                              "error": f"{type(e).__name__}: {e}"}),
                  file=sys.stderr)

    # serverless fan-out (AWSLambdaBackend analog): zillow across 4 warm
    # workers vs the 1x local number — where the driver host has few cores
    # the tasks serialize, so the delta above 1x IS the fan-out overhead
    # (spec ship + worker parse + part-file round-trip); compute scales out
    # on real deployments where each worker owns a host.
    # This process holds the chip and the workers are spawned FROM it: that
    # is only sound while the workers stay off the chip, i.e. while
    # tuplex.aws.workerPlatform keeps its default "cpu"
    # (exec/serverless._worker_env -> TUPLEX_WORKER_PLATFORM). A worker
    # platform of "tpu" would make each child fight the parent for the one
    # chip and fail or hang — drop this block before changing that default.
    if deadline is None or time.time() < deadline - 150:
        try:
            from tuplex_tpu.models import zillow as _z

            zs = []
            for i in range(4):
                p = os.path.join(cache_dir, f"zsrv_{i}.csv")
                if not os.path.exists(p):
                    _z.generate_csv(p, 100000, seed=100 + i)
                zs.append(p)
            pat = os.path.join(cache_dir, "zsrv_*.csv")
            lc = tuplex_tpu.Context()
            _z.build_pipeline(lc.csv(pat)).collect()
            t0 = time.perf_counter()
            want = _z.build_pipeline(lc.csv(pat)).collect()
            local_s = time.perf_counter() - t0
            sc = tuplex_tpu.Context({"tuplex.backend": "serverless",
                                     "tuplex.aws.maxConcurrency": 4})
            _z.build_pipeline(sc.csv(pat)).collect()   # warm pool + traces
            t0 = time.perf_counter()
            got = _z.build_pipeline(sc.csv(pat)).collect()
            srv_s = time.perf_counter() - t0
            sc.close()
            n_rows = 4 * 100000
            print(json.dumps({
                "suite": "serverless_zillow_4w", "rows": n_rows,
                "platform": "cpu-workers",
                "local_1x_s": round(local_s, 3),
                "serverless_s": round(srv_s, 3),
                "rows_per_sec": round(n_rows / srv_s, 1),
                "output_matches_local": got == want,
                "overhead_vs_local": round(srv_s / local_s, 2)}),
                file=sys.stderr)
        except Exception as e:
            print(json.dumps({"suite": "serverless_zillow_4w",
                              "error": f"{type(e).__name__}: {e}"}),
                  file=sys.stderr)


if __name__ == "__main__":
    main()
