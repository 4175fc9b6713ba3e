#!/usr/bin/env python3
"""Compare two bench JSON files and fail on regressions.

Bench output used to be checked by eyeball; this makes it a gate over two
saved result lines:

    python bench.py > old.json;  <change>;  python bench.py > new.json
    python scripts/bench_diff.py old.json new.json --threshold 0.05
    python scripts/serve_bench.py --out a.json   # likewise b.json
    python scripts/bench_diff.py a.json b.json --keys p99_s compile_s

Accepts either shape per file:
  * a raw result line ``{"metric": ..., "value": ..., ...}`` (bench.py /
    scripts/serve_bench.py stdout, or serve_bench's ``--out`` file such
    as the committed BENCH_SERVE.json);
  * a wrapper ``{"parsed": {...}, ...}`` around such a line — the
    ``parsed`` dict is compared.

Every numeric key present in BOTH files is compared with a per-key
direction (rows/s and speedups must not fall; compile seconds, transfer
bytes and latency percentiles must not rise). A move past ``--threshold``
(relative, default 10%) in the bad direction is a REGRESSION: it is
printed, and the exit code is 1 so CI and the driver can gate on it.
Improvements and within-threshold noise exit 0.
"""

from __future__ import annotations

import argparse
import json
import sys

#: direction per key: True = higher is better. Keys absent here are
#: compared informationally (printed, never a regression) because their
#: good direction is ambiguous. "value" is NOT here on purpose — the
#: primary metric's direction depends on its unit (rows/s throughput
#: rises, a latency-seconds p99 falls); see value_direction().
HIGHER_BETTER = {
    "vs_baseline": True,
    "vs_llvm": True,
    "jobs_per_s": True,
    "speedup_wall": True,
    "analyzer_inferred_ops": None,   # informational
    "compile_s": False,
    "stage_compiles": False,
    "d2h_bytes": False,
    "h2d_bytes": False,
    # device-plane cost attribution (runtime/devprof): measured device
    # seconds and the peak executable footprint must not rise; achieved
    # roofline fraction must not fall. The leaf-name rule makes the
    # per-stage dotted keys (stage_costs.0.device_s, ...) gate too.
    # flops/device_bytes are properties of the compiled graph, not
    # speed — a plan change legitimately moves them, so informational.
    "device_s": False,
    "device_cold_s": False,
    "hbm_peak": False,
    # informational: peak footprint vs the JOB's MemoryManager budget —
    # a host-side config change (tuplex.executorMemory) moves it with
    # zero device-side change, so it must not gate
    "hbm_budget_frac": None,
    "roofline_frac": True,
    "flops": None,                   # informational (plan-dependent)
    "device_bytes": None,            # informational (plan-dependent)
    "device_dispatches": None,
    # exception-plane observability (runtime/excprof): the fraction of
    # rows leaking off the compiled fast path must not grow, nor the
    # process-global drift vs the plan-time baseline — both regress like
    # perf (a rate jump means the normal-case speculation decayed). The
    # leaf-name rule gates serve_bench's per-tenant dotted twins
    # (tenants.<t>.exception_rate) too. Tier-mix fractions: rows falling
    # ALL the way to the interpreter must not grow; the exact-exit and
    # general shares are informational (a shift between them is a plan
    # change, not a regression — only the interpreter tail is pure tax).
    "exception_rate": False,
    "drift_score": False,
    # matched via the two-segment rule in direction(): the leaf
    # 'interpreter' alone is too generic to gate, so the tier-mix keys
    # register under their parent — "resolve_tier_mix.interpreter"
    # (Metrics.as_dict) and "tier_mix.interpreter" (serve_bench's
    # tenants.<t>.tier_mix.interpreter) both resolve here
    "resolve_tier_mix.interpreter": False,
    "tier_mix.interpreter": False,
    "resolve_tier_mix.exact_exit": None,
    "resolve_tier_mix.general": None,
    # latency-budget plane (runtime/critpath): the wall fraction the
    # sweep could NOT attribute must not grow (observability decaying is
    # a regression even when perf holds), nor the seconds burned on the
    # interpreter resolve tier — matched via the two-segment rule like
    # the tier-mix keys ('resolve_interpreter' could gate as a bare leaf,
    # but registering the dotted form keeps it scoped to bench budgets).
    # The other bucket seconds are informational: a plan change
    # legitimately moves time between compile/h2d/device/merge, and the
    # aggregate already gates through wall_s / p99 / rows-per-sec.
    "unattributed_frac": False,
    "latency_budget.resolve_interpreter": False,
    "coverage_frac": None,           # informational (tracks unattributed)
    "rows_seen": None,               # informational (dataset-dependent)
    # chaos drift scenario (scripts/chaos_bench.py): windows until the
    # respecialize signal trips after the shift / until health recovers
    # after the revert — detection and recovery latency gate like p99;
    # whether the signal fired at all must not fall (1 -> 0 is a break)
    "drift_trip_windows": False,
    "drift_recover_windows": False,
    "respecialize_fired": True,
    # closed-loop respecialization (serve/respec via chaos_bench's
    # respec-* classes and scripts/respec_smoke.py): trigger-to-promote
    # latency and the recovery window count gate like p99; promotions
    # must not fall (1 -> 0 means the loop stopped closing); rollback /
    # quarantine counts must not grow (a healthy candidate starting to
    # quarantine IS the regression); the residual drift after a promote
    # must not grow
    "promote_s": False,
    "respec_promotions": True,
    "respec_rollbacks": False,
    # "respec_quarantines" is deliberately NOT registered as a bare leaf:
    # the poison class INJECTS its quarantines (informational there), so
    # only the closed-loop class's two-segment form gates (leaf lookup
    # would win over the two-segment rule if both existed)
    "respec-drift.respec_quarantines": False,
    "respec_trip_jobs": False,
    "respec_promote_jobs": False,
    "drift_after_promote": False,
    "respec_markers": None,
    "analyzer_ms": False,
    "spread": False,
    "wall_s": False,
    "p50": False, "p95": False, "p99": False, "max": False, "mean": False,
    # chaos harness keys (scripts/chaos_bench.py): fault-path latency
    # gates like any other latency; recovery outcomes must not shrink
    "baseline_wall_s": False,
    "worst_over_baseline": False,    # chaos tax relative to no faults
    "jobs_ok": True,
    "jobs_failed_clean": None,       # informational (spec-dependent)
    "retries": None,                 # informational (spec-dependent)
    # static vetting (compiler/graphlint): a killed compile is a vetting
    # MISS — every wedge must be caught before submission, so
    # compiles_killed growing is a regression, while hazards_avoided may
    # grow (each one is a deadline+SIGKILL cycle that never happened).
    # graphlint_ms is the analysis cost and must not creep.
    "compiles_killed": False,
    "deadline_timeouts": False,
    "hazards_avoided": True,
    "hazards_found": None,           # informational (workload-dependent)
    "graphlint_ms": False,
    "crash_requeues": None,
}


def load_result(path: str) -> tuple[dict, dict]:
    """(flat, meta) from one bench file (wrapper or raw). Nested dicts
    (serve_bench's per-mode percentile blocks) flatten to dotted keys:
    ``concurrent.p99``. `meta` keeps the string fields ("metric",
    "unit") that decide the primary value's direction."""
    with open(path) as fp:
        data = json.load(fp)
    if isinstance(data, dict) and isinstance(data.get("parsed"), dict):
        data = data["parsed"]
    if not isinstance(data, dict):
        raise ValueError(f"{path}: not a bench result object")
    flat: dict = {}

    def walk(d: dict, prefix: str) -> None:
        for k, v in d.items():
            key = f"{prefix}{k}"
            if isinstance(v, dict):
                walk(v, key + ".")
            elif isinstance(v, (int, float)) and not isinstance(v, bool):
                flat[key] = float(v)

    walk(data, "")
    meta = {k: v for k, v in data.items() if isinstance(v, str)}
    return flat, meta


def value_direction(meta: dict):
    """Direction of the primary "value" from its declared unit: rates
    (rows/s, jobs/s, ops/s) must not fall; latency/seconds metrics must
    not rise; anything else is informational."""
    unit = str(meta.get("unit", "")).lower()
    metric = str(meta.get("metric", "")).lower()
    if "/s" in unit or "per_sec" in metric:
        return True
    if unit in ("s", "ms", "us", "seconds") or "latency" in metric:
        return False
    return None


def direction(key: str, meta: dict):
    """Direction for a (possibly dotted) key: the leaf name decides, so
    ``concurrent.p99`` compares like ``p99``; when the leaf alone is
    unknown the last TWO segments are tried (``tenants.a.tier_mix.
    interpreter`` gates like ``tier_mix.interpreter`` — 'interpreter'
    by itself is too generic to register); "value" defers to the file's
    unit/metric."""
    leaf = key.rsplit(".", 1)[-1]
    if leaf == "value":
        return value_direction(meta)
    if leaf in HIGHER_BETTER:
        return HIGHER_BETTER[leaf]
    leaf2 = ".".join(key.split(".")[-2:])
    return HIGHER_BETTER.get(leaf2, HIGHER_BETTER.get(key))


def compare(old: dict, new: dict, threshold: float,
            keys=None, meta=None) -> tuple[list, list]:
    """(rows, regressions). Each row: (key, old, new, delta_frac, verdict)."""
    rows, regressions = [], []
    meta = meta or {}
    shared = sorted(set(old) & set(new))
    if keys:
        # match full dotted keys, bare leaves, and the two-segment form
        # direction() resolves (tier_mix.interpreter under tenants.<t>.)
        shared = [k for k in shared if k in keys
                  or k.rsplit(".", 1)[-1] in keys
                  or ".".join(k.split(".")[-2:]) in keys]
    for k in shared:
        ov, nv = old[k], new[k]
        delta = (nv - ov) / abs(ov) if ov else (0.0 if nv == ov else
                                               float("inf") if nv > ov
                                               else float("-inf"))
        better = direction(k, meta)
        if better is None:
            verdict = "info"
        elif ov == 0 and nv == 0:
            verdict = "ok"
        else:
            worse = delta < -threshold if better else delta > threshold
            improved = delta > threshold if better else delta < -threshold
            verdict = ("REGRESSION" if worse
                       else "improved" if improved else "ok")
        rows.append((k, ov, nv, delta, verdict))
        if verdict == "REGRESSION":
            regressions.append(k)
    return rows, regressions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="diff two bench JSON files; exit 1 on regression")
    ap.add_argument("old", help="baseline bench JSON")
    ap.add_argument("new", help="candidate bench JSON")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="relative move counting as a regression "
                         "(default 0.10 = 10%%)")
    ap.add_argument("--keys", nargs="*", default=None,
                    help="restrict the comparison to these keys "
                         "(leaf names match dotted keys)")
    args = ap.parse_args(argv)
    try:
        old, old_meta = load_result(args.old)
        new, new_meta = load_result(args.new)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"bench_diff: {e}", file=sys.stderr)
        return 2
    if old_meta.get("metric") != new_meta.get("metric"):
        print(f"bench_diff: warning — comparing different metrics "
              f"({old_meta.get('metric')} vs {new_meta.get('metric')})",
              file=sys.stderr)
    rows, regressions = compare(old, new, args.threshold, args.keys,
                                meta=new_meta)
    if not rows:
        print("bench_diff: no shared numeric keys to compare",
              file=sys.stderr)
        return 2
    width = max(len(r[0]) for r in rows)
    for k, ov, nv, delta, verdict in rows:
        print(f"{k:<{width}}  {ov:>14.4g}  ->  {nv:>14.4g}  "
              f"{delta:>+8.1%}  {verdict}")
    if regressions:
        print(f"\nbench_diff: {len(regressions)} regression(s) past "
              f"{args.threshold:.0%}: {', '.join(regressions)}",
              file=sys.stderr)
        return 1
    print(f"\nbench_diff: OK ({len(rows)} key(s) within "
          f"{args.threshold:.0%})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
