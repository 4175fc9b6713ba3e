"""Job metrics (reference: core/include/JobMetrics.h:23-70 — compile/sample
times, fast/slow path wall time, per-row ns; exposed via
python/tuplex/metrics.py and logged per stage at LocalBackend.cc:932-949)."""

from __future__ import annotations


class Metrics:
    #: when set (a callable returning a dict), ``as_dict()['counters']``
    #: uses it instead of the process-global registry — the job service
    #: installs each job's scoped family here so a tenant's metrics never
    #: embed other tenants' transfer accounting
    counters_source = None

    def __init__(self):
        self.stages: list[dict] = []
        self.plans: list[dict] = []

    def record_stage(self, m: dict) -> None:
        self.stages.append(dict(m))

    def record_plan(self, m: dict) -> None:
        """Planning-time record: static-analyzer wall time and the number
        of operators the analyzer routed to the interpreter at PLAN time
        (compiler/analyzer.py STATS delta for one plan_stages call)."""
        self.plans.append(dict(m))

    def analyzerTimeMs(self) -> float:
        """Total UDF static-analysis wall time (ms) across plans."""
        return sum(float(m.get("analyzer_ms", 0.0)) for m in self.plans)

    def planFallbackOps(self) -> int:
        """Operators routed to the interpreter by the PLAN-time analyzer
        verdict (the emitter was never invoked for them)."""
        return sum(int(m.get("plan_fallback_ops", 0)) for m in self.plans)

    def analyzerInferredOps(self) -> int:
        """Operators whose output type the abstract interpreter
        (compiler/typeinfer.py) decided EXACTLY from the UDF AST."""
        return sum(int(m.get("analyzer_inferred_ops", 0))
                   for m in self.plans)

    def sampleTracesSkipped(self) -> int:
        """CPython sample traces schema inference skipped because the
        static verdict was exact (sample-free specialization)."""
        return sum(int(m.get("sample_traces_skipped", 0))
                   for m in self.plans)

    # -- totals (JobMetrics getters) ----------------------------------------
    @property
    def totalExceptionCount(self) -> int:
        return sum(int(m.get("exception_rows", 0)) for m in self.stages)

    def fastPathWallTime(self) -> float:
        return sum(float(m.get("fast_path_s", 0.0)) for m in self.stages)

    def slowPathWallTime(self) -> float:
        return sum(float(m.get("slow_path_s", 0.0)) for m in self.stages)

    def generalPathWallTime(self) -> float:
        """Compiled general-case (resolve) tier wall time."""
        return sum(float(m.get("general_path_s", 0.0)) for m in self.stages)

    def totalWallTime(self) -> float:
        return sum(float(m.get("wall_s", 0.0)) for m in self.stages)

    def compileTime(self) -> float:
        """Total stage-executable compile seconds (JobMetrics.h
        get_compile_time analog). Attributed per stage by the compile
        queue: inline first-dispatch compiles AND ahead-of-time pool
        compiles both count; content-addressed cache hits (in-process
        dedup or cross-process AOT artifacts) cost zero here — a fully
        warm second run reports 0.0."""
        return sum(float(m.get("compile_s", 0.0)) for m in self.stages)

    def stageCompileCount(self) -> int:
        """Number of actual XLA compiles across stages (0 on a warm AOT
        cache — the cross-process reuse proof)."""
        return sum(int(m.get("stage_compiles", 0)) for m in self.stages)

    def totalRowsOut(self) -> int:
        return sum(int(m.get("rows_out", 0)) for m in self.stages)

    def deviceTime(self) -> float:
        """Total MEASURED device seconds across stages (runtime/devprof:
        launch→ready per dispatch, cold compile waits included in the
        cold split). 0.0 when attribution is off (TUPLEX_DEVPROF=0) or
        nothing dispatched to a compiled executable."""
        return sum(float(m.get("device_s", 0.0)) for m in self.stages)

    def hbmPeak(self) -> int:
        """Largest per-execution peak device-memory footprint of any
        stage executable (XLA memory_analysis: arguments + outputs +
        temps + generated code)."""
        return max((int(m.get("hbm_peak", 0)) for m in self.stages),
                   default=0)

    def rowsSeen(self) -> int:
        """Valid input rows the stages actually processed (the
        exception-rate denominator; runtime/excprof rides it onto the
        stage record — rows_out undercounts because filters drop rows)."""
        return sum(int(m.get("rows_seen", 0)) for m in self.stages)

    def exceptionRate(self) -> float:
        """Fraction of processed rows that left the compiled fast path
        with an exception code — INCLUDING rows a resolve tier later
        retired (that is the rate the drift detector watches; terminal
        unresolved rows stay separately visible as exception_rows).
        0.0 when excprof was off or nothing ran."""
        seen = errs = 0
        for m in self.stages:
            n = int(m.get("rows_seen", 0))
            seen += n
            errs += n * float(m.get("exception_rate", 0.0))
        return (errs / seen) if seen else 0.0

    def resolveTierMix(self) -> dict:
        """Which resolve tier the deviant rows finally landed on, as
        fractions: {'exact_exit': f, 'general': f, 'interpreter': f}.
        Summed across stages from the excprof per-tier retired counts."""
        tiers = {"exact_exit": 0, "general": 0, "interpreter": 0}
        for m in self.stages:
            tiers["exact_exit"] += int(m.get("resolve_exact_rows", 0))
            tiers["general"] += int(m.get("resolve_general_rows", 0))
            tiers["interpreter"] += int(m.get("resolve_interpreter_rows",
                                              0))
        total = sum(tiers.values())
        return {k: (v / total if total else 0.0) for k, v in tiers.items()}

    def d2hBytes(self) -> int:
        """Device->host transfer bytes attributed per stage (the boundary
        transfer tax the varlen wire / handoff work is judged against)."""
        return sum(int(m.get("d2h_bytes", 0)) for m in self.stages)

    def h2dBytes(self) -> int:
        """Host->device upload bytes attributed per stage (packed dispatch
        buffers + per-leaf staging)."""
        return sum(int(m.get("h2d_bytes", 0)) for m in self.stages)

    def swapOutCount(self) -> int:
        return sum(int(m.get("swap_out", 0)) for m in self.stages)

    def swapInCount(self) -> int:
        return sum(int(m.get("swap_in", 0)) for m in self.stages)

    def swappedBytes(self) -> int:
        return sum(int(m.get("swapped_bytes", 0)) for m in self.stages)

    _STANDARD = ("wall_s", "fast_path_s", "general_path_s", "slow_path_s",
                 "rows_out", "exception_rows",
                 "swap_out", "swap_in", "swapped_bytes")

    # -- per-stage breakdown (JobMetrics.h ns/row discipline) ---------------
    def stage_breakdown(self) -> list[dict]:
        out = []
        for i, m in enumerate(self.stages):
            rows = int(m.get("rows_out", 0))
            wall = float(m.get("wall_s", 0.0))
            rec = {
                "stage": i,
                "wall_s": wall,
                "fast_path_s": float(m.get("fast_path_s", 0.0)),
                "general_path_s": float(m.get("general_path_s", 0.0)),
                "slow_path_s": float(m.get("slow_path_s", 0.0)),
                "rows_out": rows,
                "ns_per_row": (wall / rows * 1e9) if rows else 0.0,
                "exception_rows": int(m.get("exception_rows", 0)),
            }
            # backend-specific counters (compile_s, task_failures,
            # serverless_tasks, sink_rows...) survive into the breakdown;
            # never clobber derived fields, never admit bools
            for k, v in m.items():
                if k not in self._STANDARD and k not in rec \
                        and isinstance(v, (int, float)) \
                        and not isinstance(v, bool):
                    rec[k] = v
            out.append(rec)
        return out

    def as_dict(self) -> dict:
        from ..runtime import xferstats

        return {
            "stages": self.stage_breakdown(),
            "fast_path_s": self.fastPathWallTime(),
            "general_path_s": self.generalPathWallTime(),
            "slow_path_s": self.slowPathWallTime(),
            "wall_s": self.totalWallTime(),
            "device_s": self.deviceTime(),
            "hbm_peak": self.hbmPeak(),
            "compile_s": self.compileTime(),
            "stage_compiles": self.stageCompileCount(),
            "rows_out": self.totalRowsOut(),
            "exception_rows": self.totalExceptionCount,
            # exception-plane readouts (runtime/excprof): the observed
            # exception rate over rows actually processed, the resolve-
            # tier mix of the deviant rows (bench JSON flattens the dict
            # to resolve_tier_mix.* dotted keys), and the process-global
            # drift score vs the plan-time baseline
            "exception_rate": self.exceptionRate(),
            "resolve_tier_mix": self.resolveTierMix(),
            "drift_score": self._drift_score(),
            # latency-budget plane (runtime/critpath): the last job's
            # critical-path bucket vector swept from the tracing ring
            # (bench JSON flattens to latency_budget.* dotted keys);
            # empty when critpath or tracing is off
            "latency_budget": self.latencyBudget(),
            "analyzer_ms": self.analyzerTimeMs(),
            "plan_fallback_ops": self.planFallbackOps(),
            "analyzer_inferred_ops": self.analyzerInferredOps(),
            "sample_traces_skipped": self.sampleTracesSkipped(),
            "d2h_bytes": self.d2hBytes(),
            "h2d_bytes": self.h2dBytes(),
            # the tagged counter registry (runtime/xferstats): process-
            # cumulative by default; a job-service Metrics reports its
            # job's scoped family instead (counters_source)
            "counters": (self.counters_source()
                         if self.counters_source is not None
                         else xferstats.as_dict()),
        }

    @staticmethod
    def _drift_score() -> float:
        """Process-global exception-drift score (runtime/excprof EWMA vs
        the plan-time baseline); 0.0 when excprof is off or no window
        ever rolled."""
        try:
            from ..runtime import excprof

            return float(excprof.drift_score(None))
        except Exception:   # pragma: no cover - readout is best-effort
            return 0.0

    @staticmethod
    def latencyBudget() -> dict:
        """Critical-path bucket vector of the latest traced job
        (runtime/critpath sweeping the tracing ring): bucket -> seconds
        plus ``unattributed_frac``/``coverage_frac``/``dominant``. Empty
        dict when critpath is disabled (TUPLEX_CRITPATH=0), tracing
        never recorded a job span, or the sweep fails — the readout is
        best-effort and must never raise."""
        try:
            from ..runtime import critpath

            r = critpath.analyze_ring()
            if not r:
                return {}
            return {**{k: round(float(v), 6)
                       for k, v in r["buckets"].items()},
                    "unattributed_frac": round(
                        float(r["unattributed_frac"]), 4),
                    "coverage_frac": round(float(r["coverage_frac"]), 4),
                    "dominant": r["dominant"]}
        except Exception:   # pragma: no cover - readout is best-effort
            return {}

    def as_json(self) -> str:
        import json

        return json.dumps(self.as_dict())

    def export_prometheus(self) -> str:
        """Prometheus text exposition of the PROCESS-WIDE telemetry
        registry (runtime/telemetry): serve-path latency histograms,
        scheduler/memory gauges, the bridged tagged-counter families
        (runtime/xferstats) and compile-plane stats, plus the health
        state. The same text `python -m tuplex_tpu serve --metrics-port`
        serves at /metrics and the wire protocol drops as
        `<root>/metrics.prom` — this is the library entry point."""
        from ..runtime import telemetry

        return telemetry.render_prometheus()

    def export_trace(self, path: str) -> str:
        """Write the span timeline recorded so far (``tuplex.tpu.trace`` /
        TUPLEX_TRACE=1) as Chrome trace-event JSON — open in Perfetto
        (ui.perfetto.dev) or chrome://tracing. Raises RuntimeError when
        tracing never recorded anything (almost always: tracing was off)."""
        from ..runtime import tracing

        if not tracing.events():
            raise RuntimeError(
                "no spans recorded — enable tracing with tuplex.tpu.trace "
                "or TUPLEX_TRACE=1 before running the job")
        return tracing.export_chrome_trace(path)
