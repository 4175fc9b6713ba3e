"""JSON-lines job records + static HTML dashboard."""

from __future__ import annotations

import html
import json
import os
import threading
import time
import uuid
from typing import Optional


class JobRecorder:
    """Appends job/stage/exception events to <logDir>/tuplex_history.jsonl
    (reference events: job/stage/task/exception updates, thserver/rest.py)."""

    #: spans per job embedded into the history file (waterfall rendering +
    #: `python -m tuplex_tpu trace` replay); the tracing ring buffer keeps
    #: the full stream — this is the per-job slice the dashboard needs
    SPAN_EVENT_CAP = 400

    def __init__(self, log_dir: str, enabled: bool = True,
                 exception_display_limit: int = 5):
        self.exception_display_limit = exception_display_limit
        self.enabled = enabled
        self.path = os.path.join(log_dir or ".", "tuplex_history.jsonl")
        self.job_id = uuid.uuid4().hex[:12]
        self._stage_no = 0
        self._warned_write = False
        self._trace_mark = 0.0

    def _new_job(self) -> None:
        self.job_id = uuid.uuid4().hex[:12]
        self._stage_no = 0

    def _write(self, rec: dict) -> None:
        if not self.enabled:
            return
        # an explicit job id wins: the job SERVICE (serve/) interleaves
        # many concurrent jobs through one recorder, so its events carry
        # their own id instead of riding the single-job cursor
        rec.setdefault("job", self.job_id)
        rec["ts"] = round(time.time(), 3)
        try:
            with open(self.path, "a") as fp:
                fp.write(json.dumps(rec, default=str) + "\n")
        except OSError as e:
            # warn ONCE so a bad logDir is diagnosable, then stay quiet —
            # a recorder failure must never spam or kill the job
            if not self._warned_write:
                self._warned_write = True
                from ..utils.logging import get_logger

                get_logger("history").warning(
                    "history write to %s failed (%s: %s); further "
                    "failures will be silent", self.path,
                    type(e).__name__, e)

    def job_started(self, action: str, plan: list,
                    trace_mark: Optional[float] = None) -> None:
        self._new_job()  # each action is its own job in the dashboard
        from ..runtime import tracing

        # job_done slices spans from here; the caller passes a mark taken
        # BEFORE its job span opened so job/plan spans make the slice
        self._trace_mark = trace_mark if trace_mark is not None \
            else tracing.now_us()
        previews = []
        if self.enabled:
            from ..plan.logical import preview_sample_exceptions

            for st in plan:
                for op in getattr(st, "ops", []) or []:
                    # on-demand preview pass for operators whose schema
                    # came statically (sample-free specialization skipped
                    # the trace the previews used to ride on); traced ops
                    # return their recorded previews unchanged
                    try:
                        excs = preview_sample_exceptions(op)
                    except Exception:   # pragma: no cover - advisory
                        excs = list(getattr(op, "sample_exceptions", [])
                                    or [])
                    for exc_name, row_repr in excs[
                            : self.exception_display_limit]:
                        previews.append({"op": type(op).__name__,
                                         "op_id": op.id,
                                         "exc": exc_name, "row": row_repr})
        self._write({"event": "job_start", "action": action,
                     "stages": [type(s).__name__ for s in plan],
                     # sample-time exception previews (reference:
                     # SampleProcessor feeding the webui BEFORE execution)
                     "sample_exception_previews": previews,
                     # per-operator static-analyzer findings (the lint-
                     # driven authoring loop: `python -m tuplex_tpu lint`
                     # verdicts rendered per op in the dashboard)
                     "lint": _plan_lint_findings(plan)})

    def stage_started(self, stage) -> None:
        """LIVE event: a stage began executing (reference: the driver posts
        task/stage updates to the history server DURING the job,
        HistoryServerConnector.cc:102-198 — not only at completion).
        Carries the fused op count and the plan's predicted compile
        seconds (where the platform has a curve) so a dashboard watcher
        can tell a long compile from a hung stage BEFORE the stage
        completes."""
        rec = {"event": "stage_start", "no": self._stage_no + 1,
               "kind": type(stage).__name__}
        ops = getattr(stage, "ops", None)
        if ops:
            rec["n_ops"] = len(ops)
            pred = getattr(stage, "predicted_compile_s", None)
            if pred is not None:
                rec["predicted_compile_s"] = round(float(pred), 3)
            # plan-time resolve-tier pick (plan/physical.ResolvePlan):
            # which resolve tiers this stage can reach, decided from the
            # analyzer inventory before any row executes
            try:
                rec["resolve_tier"] = stage.resolve_plan().tier
            except Exception:   # pragma: no cover - advisory surface
                pass
            # plan-time static-vetting verdict (compiler/graphlint): the
            # hazard score — and, for a vetoed wedge, WHICH rule fired —
            # visible before the stage runs a single row
            rep = getattr(stage, "graph_report", None)
            if rep is not None:
                rec["hazard_score"] = round(min(rep.hazard_score, 1e9), 2)
            if getattr(stage, "hazard_rule", None):
                rec["hazard_rule"] = stage.hazard_rule
        self._write(rec)
        self._last_progress = 0.0

    def task_progress(self, parts_done: int, rows: int) -> None:
        """LIVE event: partition-level progress inside the running stage.
        Throttled (0.2s) so tight partition loops don't swamp the log."""
        now = time.time()
        if now - getattr(self, "_last_progress", 0.0) < 0.2:
            return
        self._last_progress = now
        self._write({"event": "progress", "no": self._stage_no + 1,
                     "parts": parts_done, "rows": rows})

    def stage_done(self, stage, metrics: dict, exceptions: list) -> None:
        self._stage_no += 1
        sample = [(getattr(e, "trace", None) or repr(e))[:800]
                  for e in exceptions[: self.exception_display_limit]]
        self._write({"event": "stage", "no": self._stage_no,
                     "kind": type(stage).__name__,
                     "metrics": metrics, "exception_sample": sample})

    def worker_task_event(self, task: int, rec: dict) -> None:
        """LIVE event from a fan-out worker (started / finished, rows,
        exception count) — streamed off the task's events.jsonl by the
        driver's poll loop, so remote tasks are visible in the dashboard
        WHILE the job runs (reference: executors push per-task status to
        the history server, HistoryServerConnector.cc:102-198)."""
        self._write({**{k: v for k, v in rec.items()
                        if k not in ("event", "task", "kind", "no")},
                     "event": "task", "task": task,
                     "no": self._stage_no + 1,
                     "kind": rec.get("event", "update")})

    def job_done(self, rows: int, wall_s: float, exc_counts: dict) -> None:
        self._write_job_spans()
        self._write_excprof()
        self._write({"event": "job_done", "rows": rows,
                     "wall_s": round(wall_s, 4),
                     "exception_counts": exc_counts})

    def _write_excprof(self) -> None:
        """Embed the exception-plane readout (runtime/excprof) into the
        history file at the job's terminal turn: per-stage x code x op
        counts + resolve-tier mix vs the plan-time baseline, the global
        drift readout, and the sampled deviant rows — the dashboard drift
        panel and the `excstats` CLI read it from here. The counters are
        live process state (cumulative across jobs sharing the process),
        so the panel is a snapshot AT this job's end, not a per-job
        delta; serve jobs instead get per-tenant rows from the service's
        own terminal event."""
        if not self.enabled:
            return
        try:
            from ..core.errors import exception_name
            from ..runtime import excprof

            if not excprof.enabled():
                return
            reps = excprof.reports()
            if not reps:
                return
            stages = {}
            for key, r in reps.items():
                d = {"rows": r["rows"], "rate": round(r["rate"], 4),
                     "fallback": r["fallback"],
                     "unexpected": r["unexpected"],
                     "codes": {f"{exception_name(c)}#op{op}": n
                               for (c, op), n in sorted(r["codes"].items())},
                     "tiers": r["tiers"]}
                base = r.get("baseline")
                if base is not None:
                    d["baseline"] = {
                        "codes": [exception_name(c)
                                  for c in base["codes"]],
                        "tier": base["tier"], "pruned": base["pruned"]}
                stages[key] = d
            samples: dict = {}
            for (key, code), caps in excprof.samples().items():
                samples.setdefault(key, {})[exception_name(code)] = caps
            self._write({"event": "excprof",
                         "drift": excprof.scope_report(None),
                         "stages": stages, "samples": samples})
        except Exception:   # pragma: no cover - the panel is advisory
            pass

    def serve_job_event(self, job_id: str, event: str, **fields) -> None:
        """Dashboard row for a JOB-SERVICE job (serve/): same event shapes
        as the single-job path (`job_start`/`stage`/`job_done`) but keyed
        by the service job's own id, so N concurrent tenants render as N
        independent job rows instead of colliding on the recorder's
        cursor."""
        self._write({**fields, "event": event, "job": str(job_id)})

    def respec_event(self, tenant: str, phase: str, **fields) -> None:
        """Re-specialization lifecycle row (serve/respec): one record per
        per-tenant transition — trigger / candidate-ready / canary-start
        / promote / quarantine / rollback — keyed by a synthetic
        per-tenant job id so the dashboard renders each tenant's plan-
        generation history as its own timeline."""
        self._write({**fields, "event": "respec", "phase": str(phase),
                     "tenant": str(tenant),
                     "job": f"respec:{tenant}"})

    def _write_job_spans(self) -> None:
        """Embed this job's span slice (runtime/tracing, when enabled) into
        the history file — the dashboard waterfall and the `trace` CLI
        replay read it from here, so the timeline survives the process."""
        if not self.enabled:
            return
        from ..runtime import tracing

        evts = tracing.events_since(self._trace_mark)
        if not evts:
            return
        # the `job` span is still open on this thread (it closes after this
        # turn, so that it covers the boxing of the rows and this write):
        # embed it as it stands, so the slice keeps its root
        now, tid = tracing.now_us(), threading.get_ident()
        mine = [s for s in tracing.open_spans()
                if s["tid"] == tid and s["ts"] >= self._trace_mark]
        evts = evts + [dict(s, dur=now - s["ts"], depth=d)
                       for d, s in enumerate(mine)]
        spans, n_total, n_dropped = _span_slice(evts, self.SPAN_EVENT_CAP)
        self._write({"event": "spans", "n_total": n_total,
                     "n_dropped": n_dropped, "spans": spans})

    def serve_job_spans(self, job_id: str, evts: list,
                        tenant: Optional[str] = None) -> None:
        """Embed a JOB-SERVICE job's tenant-tagged span stream
        (``tracing.events_for_stream(job_id)``) keyed by the job's own id,
        so serve jobs get the same dashboard waterfall and `python -m
        tuplex_tpu trace` replay lane as single-job runs — previously only
        in-process jobs' streams survived into the replay."""
        if not self.enabled or not evts:
            return
        spans, n_total, n_dropped = _span_slice(evts, self.SPAN_EVENT_CAP)
        rec = {"event": "spans", "job": str(job_id), "n_total": n_total,
               "n_dropped": n_dropped, "spans": spans}
        if tenant is not None:
            rec["tenant"] = tenant
        self._write(rec)


def _span_slice(evts: list, cap: int) -> tuple:
    """The embedded per-job span slice: (spans, n_total, n_dropped).
    Past `cap` events, truncate DEEPEST-SUBTREE-FIRST: only spans that are
    currently leaves of the containment forest are eligible to drop
    (deepest first, shortest first within a depth), and dropping a span
    can make its parent a leaf for the next round — so the embedded slice
    is always a connected tree. A keep-by-duration policy would sever
    trees: a long leaf could survive while its shorter parent dropped,
    and every consumer that reconstructs the hierarchy by containment
    (the dashboard waterfall, the `trace` replay, runtime/critpath's
    orphan detection) would misfile the orphan as degraded input.
    Structural spans (job, stage executes, compiles) are interior nodes,
    so they survive by construction. Truncation is never silent: the
    dropped count rides the record (the waterfall panel renders it) and
    bumps the ``trace_spans_dropped`` counter (runtime/xferstats —
    visible in Metrics counters and the Prometheus scrape)."""
    n_total = len(evts)
    n_dropped = max(0, n_total - cap)
    if n_dropped:
        evts = _prune_deepest(evts, n_dropped)
        from ..runtime import xferstats

        xferstats.bump("trace_spans_dropped", n_dropped, tag="embed_cap")
    spans = [{"name": e["name"], "cat": e.get("cat", ""),
              "ts": round(float(e["ts"]), 1),
              "dur": round(float(e["dur"]), 1)
              if e.get("dur") is not None else 0.0,
              "tid": e.get("tid", 0), "depth": e.get("depth", 0),
              **({"args": e["args"]} if e.get("args") else {})}
             for e in evts]
    return spans, n_total, n_dropped


def _prune_deepest(evts: list, n_drop: int) -> list:
    """Drop exactly ``n_drop`` spans, leaves-of-the-containment-forest
    first (deepest, then shortest), so what remains is always a connected
    tree per thread lane. Parent links come from interval containment on
    each tid's timeline — the same reconstruction the waterfall uses —
    not from the recorded ``depth`` field, so a slice stays connected
    even when cross-thread spans carry surprising depths."""
    import heapq

    order = sorted(range(len(evts)),
                   key=lambda i: (evts[i].get("tid", 0),
                                  float(evts[i]["ts"]),
                                  -(evts[i].get("dur") or 0.0)))
    parent = [-1] * len(evts)
    nkids = [0] * len(evts)
    sdepth = [0] * len(evts)
    stack: list = []          # open-span indices for the current tid
    cur_tid = object()
    eps = 0.05                # µs slack for rounded/coincident edges
    for i in order:
        e = evts[i]
        tid = e.get("tid", 0)
        if tid != cur_tid:
            cur_tid, stack = tid, []
        ts = float(e["ts"])
        end = ts + float(e.get("dur") or 0.0)
        # pop every frame this span is NOT contained in — handles both
        # disjoint predecessors and partial overlap (a straddling span
        # becomes a sibling of the frame it overlaps, not its child)
        while stack and end > stack[-1][1] + eps:
            stack.pop()
        if stack:
            parent[i] = stack[-1][0]
            nkids[parent[i]] += 1
            sdepth[i] = sdepth[parent[i]] + 1
        else:
            # no containment parent: drop at the recorded depth so a
            # cross-thread orphan still yields before shallower spans
            sdepth[i] = int(e.get("depth") or 0)
        stack.append((i, end))
    dropped = [False] * len(evts)
    # heapq is a min-heap: (-depth, dur) pops deepest-then-shortest first
    heap = [(-sdepth[i], evts[i].get("dur") or 0.0, i)
            for i in range(len(evts)) if nkids[i] == 0]
    heapq.heapify(heap)
    left = n_drop
    while left > 0 and heap:
        _, _, i = heapq.heappop(heap)
        dropped[i] = True
        left -= 1
        p = parent[i]
        if p >= 0:
            nkids[p] -= 1
            if nkids[p] == 0 and not dropped[p]:
                heapq.heappush(
                    heap, (-sdepth[p], evts[p].get("dur") or 0.0, p))
    return sorted((evts[i] for i in range(len(evts)) if not dropped[i]),
                  key=lambda e: e["ts"])


_LINT_CAP = 80


def _plan_lint_findings(plan: list) -> list:
    """Per-operator static-analyzer findings for the job_start record
    (compiler/analyzer.py UDFReports, already memoized on the stages).
    Best-effort: a lint failure must never block a job from starting."""
    out: list = []
    for st in plan:
        reports = getattr(st, "udf_reports", None)
        if reports is None:
            continue
        try:
            for op, attr, rep in reports():
                # "statically typed: yes/no + why not" per operator
                # (sample-free specialization, compiler/typeinfer.py)
                tl = rep.typed_line()
                if tl is not None and len(out) < _LINT_CAP:
                    out.append({
                        "op": type(op).__name__, "op_id": op.id,
                        "udf": f"{rep.name}.{attr}" if attr != "udf"
                        else rep.name,
                        "kind": "typed", "reason": tl,
                        "loc": f"{rep.filename}:{rep.line_base}",
                        "conditional": False})
                for f in rep.findings:
                    if len(out) >= _LINT_CAP:
                        return out
                    out.append({
                        "op": type(op).__name__, "op_id": op.id,
                        "udf": f"{rep.name}.{attr}" if attr != "udf"
                        else rep.name,
                        "kind": f.kind, "reason": f.reason,
                        "loc": rep.loc(f),
                        "conditional": bool(f.conditional)})
            dead = getattr(st, "dead_resolver_findings", None)
            if dead is not None:
                for rop, gop, reason in dead():
                    if len(out) >= _LINT_CAP:
                        return out
                    out.append({
                        "op": type(rop).__name__, "op_id": rop.id,
                        "udf": f"guards #{gop.id}",
                        "kind": "dead-resolver", "reason": reason,
                        "loc": "", "conditional": False})
            sug = getattr(st, "resolver_suggestions", None)
            if sug is not None:
                # positive twin of the dead-resolver row: the inventory
                # proves only exact Python classes can fire, yet no
                # resolver is attached
                for reason in sug():
                    if len(out) >= _LINT_CAP:
                        return out
                    out.append({
                        "op": type(st).__name__, "op_id": "-",
                        "udf": "", "kind": "suggestion",
                        "reason": reason, "loc": "",
                        "conditional": False})
        except Exception:   # pragma: no cover - lint is advisory
            continue
    return out


def _fmt_eng(v) -> str:
    """Engineering-notation cell for the device-utilization table
    (flops/bytes counts), em-dash when absent; the ladder itself is
    shared with compilestats (runtime/devprof.fmt_eng)."""
    if v is None:
        return "—"
    from ..runtime.devprof import fmt_eng

    return fmt_eng(v)


def _excprof_html(ev: dict) -> str:
    """Exception-plane drift panel for one job: drift score vs the
    plan-time baseline (bar + respecialize badge), resolve-tier mix,
    per-stage x code counts against the expected inventory, and the
    sampled deviant rows. Renders both shapes: the single-job recorder's
    terminal `excprof` event (drift/stages/samples) and the job
    service's per-tenant row (flat scope_report fields + tenant)."""
    drift = ev.get("drift") or ev
    score = float(drift.get("drift_score", 0.0) or 0.0)
    resp = bool(drift.get("respecialize_recommended"))
    rate = float(drift.get("exception_rate", 0.0) or 0.0)
    mix = drift.get("tier_mix") or {}
    tenant = ev.get("tenant")
    pct = max(0.0, min(1.0, score)) * 100
    # the respecialize badge is a LIFECYCLE now (serve/respec): when the
    # service's controller annotated this row, show where the tenant is
    # in drift → candidate → canary → promote/quarantine instead of the
    # bare recommendation
    rstate = ev.get("respec_state")
    rgen = ev.get("respec_generation")
    if rstate and (rstate != "idle" or resp):
        label = f"respec: {rstate}"
        if rgen:
            label += f" (gen {rgen})"
        badge = f' <span class=respbadge>{html.escape(label)}</span>'
    else:
        badge = (' <span class=respbadge>respecialize recommended</span>'
                 if resp else "")
    mix_s = ", ".join(f"{k} {v * 100:.1f}%" for k, v in sorted(mix.items())
                      if v) or "—"
    who = f"tenant {html.escape(str(tenant))}" if tenant else "global"
    head = (f"exception plane — {who}: drift "
            f"<span class=driftbar><span class=driftfill "
            f"style=\"width:{pct:.1f}%\"></span></span> {score:.2f}"
            f"{badge} · exc rate {rate * 100:.2f}% · tier mix {mix_s}")
    body: list = []
    stages = ev.get("stages") or {}
    if stages:
        body.append("<table class=exctab><tr><th>stage</th><th>rows</th>"
                    "<th>exc rate</th><th>unexpected</th>"
                    "<th>codes (observed)</th><th>expected</th>"
                    "<th>tiers</th></tr>")
        for key, s in sorted(stages.items()):
            codes = ", ".join(f"{c}:{n}" for c, n in
                              sorted((s.get("codes") or {}).items())) or "—"
            tiers = ", ".join(f"{t}:{n}" for t, n in
                              sorted((s.get("tiers") or {}).items())) or "—"
            base = s.get("baseline") or {}
            exp = ", ".join(base.get("codes") or []) or "none"
            if base.get("tier"):
                exp += f" → {base['tier']}"
            unexpected = int(s.get("unexpected", 0))
            ucls = " class=unexp" if unexpected else ""
            body.append(
                f"<tr><td><code>{html.escape(str(key)[:16])}</code></td>"
                f"<td>{s.get('rows', 0)}</td>"
                f"<td>{float(s.get('rate', 0.0)) * 100:.2f}%</td>"
                f"<td{ucls}>{unexpected}</td>"
                f"<td>{html.escape(codes)}</td>"
                f"<td>{html.escape(exp)}</td>"
                f"<td>{html.escape(tiers)}</td></tr>")
        body.append("</table>")
    for key, by_code in sorted((ev.get("samples") or {}).items()):
        for code, caps in sorted(by_code.items()):
            for r in caps:
                body.append(
                    f"<div class=excsample>↳ <b>{html.escape(str(code))}"
                    f"</b> @ <code>{html.escape(str(key)[:16])}</code>: "
                    f"{html.escape(str(r))}</div>")
    return (f"<details class=excplane><summary>{head}</summary>"
            f"{''.join(body)}</details>")


def _critpath_html(ev: dict) -> str:
    """Latency-budget panel for one job (runtime/critpath `critpath`
    event): the exclusive bucket vector as a proportional budget strip +
    table against the tenant's EWMA baseline, the slow-job blame verdict,
    and the SLO line when one is declared. The same numbers `python -m
    tuplex_tpu whyslow` prints — the panels must agree because they read
    the same record."""
    buckets = ev.get("buckets") or {}
    wall = float(ev.get("wall_s") or 0.0)
    if not buckets or wall <= 0:
        return ""
    tenant = ev.get("tenant")
    who = f"tenant {html.escape(str(tenant))}" if tenant else "job"
    dom = str(ev.get("dominant") or "?")
    cov = float(ev.get("coverage_frac") or 0.0) * 100
    badge = ""
    if ev.get("slow"):
        blame = str(ev.get("blame") or "?")
        badge = (f' <span class=slowbadge>SLOW — blame '
                 f'{html.escape(blame)}</span>')
    if ev.get("degraded"):
        badge += ' <span class=degbadge>degraded trace</span>'
    slo = ""
    if float(ev.get("slo_ms") or 0.0) > 0:
        ok = ev.get("slo_ok")
        slo = (f" · SLO {float(ev['slo_ms']):.0f}ms "
               f"{'met' if ok else 'MISSED' if ok is not None else '?'}")
    head = (f"latency budget — {who}: wall {wall * 1e3:.1f}ms, dominant "
            f"<b>{html.escape(dom)}</b>, coverage {cov:.1f}%{slo}{badge}")
    # proportional budget strip: one segment per nonzero bucket, in
    # canonical order, colored like the waterfall categories
    strip, left = [], 0.0
    order = [b for b in _CP_ORDER if b in buckets] + \
            [b for b in buckets if b not in _CP_ORDER]
    for b in order:
        frac = float(buckets.get(b) or 0.0) / wall
        if frac <= 0:
            continue
        w = min(frac, 1.0 - left / 100.0) * 100.0
        strip.append(f'<span class="cpseg cp-{html.escape(b)}" '
                     f'style="left:{left:.2f}%;width:{max(w, 0.1):.2f}%" '
                     f'title="{html.escape(b)} '
                     f'{float(buckets[b]) * 1e3:.1f}ms"></span>')
        left += w
    base = ev.get("baseline") or {}
    rows = ["<table class=cptab><tr><th>bucket</th><th>ms</th>"
            "<th>share</th><th>baseline ms</th><th>Δ ms</th></tr>"]
    for b in order:
        v = float(buckets.get(b) or 0.0)
        bl = base.get(b)
        if v <= 0 and not bl:
            continue
        cls = " class=cpdom" if b == dom else ""
        if ev.get("slow") and b == ev.get("blame"):
            cls = " class=cpblame"
        d = "" if bl is None else f"{(v - float(bl)) * 1e3:+.1f}"
        rows.append(
            f"<tr{cls}><td><code>{html.escape(b)}</code></td>"
            f"<td>{v * 1e3:.1f}</td><td>{v / wall * 100:.1f}%</td>"
            f"<td>{'—' if bl is None else f'{float(bl) * 1e3:.1f}'}</td>"
            f"<td>{d or '—'}</td></tr>")
    rows.append("</table>")
    return (f"<details class=critpath><summary>{head}</summary>"
            f"<div class=cptrack>{''.join(strip)}</div>"
            f"{''.join(rows)}</details>")


# canonical bucket order for the budget panel (mirrors critpath.BUCKETS
# without importing the runtime module into the static dashboard path)
_CP_ORDER = ("admission_wait", "queue_wait", "compile_trace",
             "compile_lower", "compile_xla", "h2d", "device",
             "resolve_general", "resolve_interpreter", "d2h", "merge",
             "scheduler_other", "unattributed")


_WF_CAP = 120      # bars per job (longest-first keeps the picture honest)


def _waterfall_html(sp_ev: dict, cp_ev: Optional[dict] = None) -> str:
    """Span waterfall for one job: proportional bars over the job's trace
    window, indented by nesting depth, colored by category. When the
    job's `critpath` record is available, bars owning a critical-path
    segment get the `onpath` outline so the budget panel's attribution
    is visible in the timeline itself."""
    spans = sp_ev.get("spans", [])
    if not spans:
        return ""
    t0 = min(s["ts"] for s in spans)
    t1 = max(s["ts"] + (s.get("dur") or 0.0) for s in spans)
    total = max(t1 - t0, 1e-6)
    shown = sorted(spans, key=lambda s: -(s.get("dur") or 0.0))[:_WF_CAP]
    shown.sort(key=lambda s: (s["ts"], s.get("depth", 0)))
    # critical-path segments from the budget record: [ts, dur, bucket,
    # name] on the same trace clock as the embedded spans
    path = (cp_ev or {}).get("path") or []
    bars = []
    n_onpath = 0
    for s in shown:
        left = (s["ts"] - t0) / total * 100.0
        width = max((s.get("dur") or 0.0) / total * 100.0, 0.15)
        dur_ms = (s.get("dur") or 0.0) / 1e3
        cat = str(s.get("cat") or "exec")
        s_end = s["ts"] + (s.get("dur") or 0.0)
        onpath = any(p[3] == s["name"] and p[0] >= s["ts"] - 0.2
                     and p[0] + p[1] <= s_end + 0.2 for p in path)
        n_onpath += onpath
        label = f"{s['name']} {dur_ms:.1f}ms"
        indent = int(s.get("depth", 0)) * 10
        bars.append(
            f'<div class=wfrow style="padding-left:{indent}px">'
            f'<span class=wflabel>{html.escape(label)}</span>'
            f'<span class=wftrack><span class="wfbar cat-'
            f'{html.escape(cat)}{" onpath" if onpath else ""}" '
            f'style="left:{left:.2f}%;'
            f'width:{width:.2f}%"></span></span></div>')
    n_total = sp_ev.get("n_total", len(spans))
    n_dropped = sp_ev.get("n_dropped", 0)
    head = (f"span waterfall — {len(shown)} of {n_total} span(s) shown, "
            f"{total / 1e3:.1f}ms window")
    if n_onpath:
        head += f", {n_onpath} on the critical path (outlined)"
    if n_dropped:
        # the recorder capped the embedded slice: say so instead of
        # letting a truncated panel read as the whole timeline
        head += (f" ({n_dropped} shortest span(s) dropped at the "
                 f"{len(spans)}-span embed cap)")
    return (f"<details open class=waterfall><summary>{html.escape(head)}"
            f"</summary>{''.join(bars)}</details>")


def render_report(log_dir: str = ".", out_path: Optional[str] = None) -> str:
    """Static HTML dashboard over the history file (webui analog)."""
    out_path = out_path or os.path.join(log_dir or ".",
                                        "tuplex_history.html")
    with open(out_path, "w") as fp:
        fp.write(_render_doc(log_dir, live=False))
    return out_path


def _load_jobs(log_dir: str) -> dict:
    """Parse <logDir>/tuplex_history.jsonl into {job_id: [events]} (insert
    order preserved; undecodable lines skipped). Shared by the dashboard
    and the Chrome-trace replay so the two read one format."""
    src = os.path.join(log_dir or ".", "tuplex_history.jsonl")
    jobs: dict = {}
    if not os.path.exists(src):
        raise FileNotFoundError(src)
    with open(src) as fp:
        for line in fp:
            try:
                r = json.loads(line)
            except json.JSONDecodeError:
                continue
            jobs.setdefault(r.get("job", "?"), []).append(r)
    return jobs


def _render_doc(log_dir: str, live: bool) -> str:
    """Dashboard document; `live` adds the auto-refresh tag (served pages
    only — the on-disk report stays a static archival artifact)."""
    src = os.path.join(log_dir or ".", "tuplex_history.jsonl")
    try:
        jobs = _load_jobs(log_dir)
    except FileNotFoundError:
        jobs = {}

    rows_html = []
    for job_id, events in jobs.items():
        if job_id.startswith("respec:"):
            # re-specialization lifecycle lane (serve/respec): the
            # tenant's plan-generation history as one timeline row —
            # drift trigger → candidate → canary → promote/quarantine
            revs = [e for e in events if e.get("event") == "respec"]
            if revs:
                tenant = revs[0].get("tenant", job_id[len("respec:"):])
                steps = []
                for e in revs[-16:]:
                    s = str(e.get("phase", "?"))
                    if e.get("gen") is not None:
                        s += f" g{e['gen']}"
                    if e.get("reason"):
                        s += f" ({html.escape(str(e['reason'])[:60])})"
                    steps.append(html.escape(s) if "(" not in s else s)
                last = revs[-1]
                rows_html.append(
                    f"<tr class=respec><td colspan=7>⟳ respec lifecycle"
                    f" — tenant <code>{html.escape(str(tenant))}</code>"
                    f" [{html.escape(str(last.get('phase', '?')))}]: "
                    f"{' → '.join(steps)}</td></tr>")
            continue
        done = next((e for e in events if e["event"] == "job_done"), {})
        stages = [e for e in events if e["event"] == "stage"]
        start = next((e for e in events if e["event"] == "job_start"), {})
        excs = done.get("exception_counts") or {}
        fast = sum(e["metrics"].get("fast_path_s", 0) for e in stages)
        slow = sum(e["metrics"].get("slow_path_s", 0) for e in stages)
        if not done and live:
            # in-flight job on a LIVE poll: surface the stage_start/
            # progress events (the reference webui's live task updates).
            # The static archival report keeps the plain row — a crashed
            # job must not read as perpetually RUNNING there.
            n_stages = len(start.get("stages", [])) or "?"
            cur = max((e["no"] for e in events
                       if e["event"] in ("stage_start", "stage")), default=0)
            prog = next((e for e in reversed(events)
                         if e["event"] == "progress"), {})
            status = (f"RUNNING — stage {cur}/{n_stages}, "
                      f"{prog.get('parts', 0)} partition(s), "
                      f"{prog.get('rows', 0)} rows so far")
            rows_html.append(
                f"<tr class=running><td><code>{html.escape(job_id)}"
                f"</code></td><td>{len(stages)}</td>"
                f"<td colspan=4>{html.escape(status)}</td>"
                f"<td>—</td></tr>")
        else:
            rows_html.append(
                f"<tr><td><code>{html.escape(job_id)}</code></td>"
                f"<td>{len(stages)}</td>"
                f"<td>{done.get('rows', '—')}</td>"
                f"<td>{done.get('wall_s', '—')}</td>"
                f"<td>{fast:.3f}</td><td>{slow:.3f}</td>"
                f"<td>{html.escape(json.dumps(excs)) if excs else '—'}"
                f"</td></tr>")
        # per-task rows from fan-out workers (serverless/multihost)
        tasks: dict = {}
        for e in events:
            if e.get("event") == "task":
                # key on (stage, task): a job with several fan-out stages
                # reuses task numbers per stage
                tasks.setdefault((e.get("no"), e.get("task")), []).append(e)
        multi_stage = len({k[0] for k in tasks}) > 1
        for t in sorted(tasks, key=lambda x: (x[0] is None, x[0],
                                              x[1] is None, x[1])):
            last = tasks[t][-1]
            if last.get("kind") == "done":
                desc = (f"done — {last.get('rows', '?')} rows, "
                        f"{last.get('exceptions', 0)} exception(s), "
                        f"{last.get('wall_s', '?')}s")
            elif last.get("kind") == "fallback":
                desc = (f"failed after {last.get('attempt', '?')} "
                        f"attempt(s) — completed on the driver")
            else:
                desc = f"{last.get('kind', 'running')} (pid {last.get('pid', '?')})"
            label = (f"stage {t[0]} task {t[1]}" if multi_stage
                     else f"task {t[1]}")
            rows_html.append(
                f"<tr class=task><td colspan=7>&nbsp;&nbsp;"
                f"{html.escape(label)}: {html.escape(desc)}</td></tr>")
        # per-stage device utilization (runtime/devprof metrics riding the
        # stage record): measured device seconds, XLA flops/bytes, peak
        # executable footprint and the achieved roofline fraction
        dev = [e for e in stages if e["metrics"].get("device_s")]
        if dev:
            cells = ["<table class=devtab><tr><th>stage</th>"
                     "<th>device s</th><th>dispatches</th><th>FLOPs</th>"
                     "<th>bytes</th><th>peak mem</th><th>roofline</th>"
                     "</tr>"]
            for e in dev:
                m = e["metrics"]
                rf = m.get("roofline_frac")
                bar = ""
                if rf is not None:
                    pct = max(0.0, min(1.0, float(rf))) * 100
                    bar = (f"<span class=rlbar><span class=rlfill "
                           f"style=\"width:{pct:.2f}%\"></span></span> "
                           f"{pct:.2f}%")
                cells.append(
                    f"<tr><td>{e.get('no', '?')} "
                    f"[{html.escape(str(e.get('kind', '')))}]</td>"
                    f"<td>{m.get('device_s', 0):.4f}</td>"
                    f"<td>{int(m.get('device_dispatches', 0))}</td>"
                    f"<td>{_fmt_eng(m.get('flops'))}</td>"
                    f"<td>{_fmt_eng(m.get('device_bytes'))}</td>"
                    f"<td>{_fmt_eng(m.get('hbm_peak'))}</td>"
                    f"<td>{bar or '—'}</td></tr>")
            cells.append("</table>")
            rows_html.append(
                f"<tr class=dev><td colspan=7><details><summary>device "
                f"utilization — {len(dev)} stage(s)</summary>"
                f"{''.join(cells)}</details></td></tr>")
        # static-vetting verdicts (compiler/graphlint metrics riding the
        # stage record): lint cost and the hazards found/avoided per
        # stage — a vetoed wedge shows up HERE, not as a compile kill
        for e in stages:
            m = e["metrics"]
            if not (m.get("hazards_found") or m.get("hazards_avoided")
                    or m.get("graphlint_ms")):
                continue
            rule = m.get("hazard_rule", "")
            desc = (f"graphlint {m.get('graphlint_ms', 0):.1f} ms — "
                    f"{int(m.get('hazards_found', 0))} hazard(s) found, "
                    f"{int(m.get('hazards_avoided', 0))} compile(s) "
                    f"avoided")
            if rule:
                desc += f" (rule {rule})"
            rows_html.append(
                f"<tr class=lint><td colspan=7>⚠ stage {e.get('no', '?')}"
                f" [{html.escape(str(e.get('kind', '')))}]: "
                f"{html.escape(desc)}</td></tr>")
        for e in stages:
            for s in e.get("exception_sample", []):
                rows_html.append(
                    f"<tr class=exc><td colspan=7>↳ "
                    f"{html.escape(s)}</td></tr>")
        # per-operator lint findings (job_start 'lint': the static
        # analyzer's verdicts, rendered like the reference webui's
        # per-operator detail rows)
        for f in start.get("lint", []) or []:
            cold = " [cold-arm]" if f.get("conditional") else ""
            rows_html.append(
                f"<tr class=lint><td colspan=7>⚐ "
                f"{html.escape(str(f.get('op', '?')))}"
                f"#{html.escape(str(f.get('op_id', '?')))} "
                f"{html.escape(str(f.get('udf', '')))} — "
                f"<b>{html.escape(str(f.get('kind', '')))}</b>: "
                f"{html.escape(str(f.get('reason', '')))}"
                f" ({html.escape(str(f.get('loc', '')))}){cold}</td></tr>")
        # exception-plane drift panel (runtime/excprof): the terminal
        # `excprof` event — the single-job recorder's full readout or
        # the job service's per-tenant scope_report row
        exev = next((e for e in reversed(events)
                     if e.get("event") == "excprof"), None)
        if exev:
            rows_html.append(
                f"<tr class=excp><td colspan=7>{_excprof_html(exev)}"
                f"</td></tr>")
        # latency-budget panel (runtime/critpath `critpath` event): the
        # exclusive bucket vector, blame verdict and SLO line — rendered
        # before the waterfall so the budget reads first, and handed to
        # the waterfall so critical-path bars get the outline
        cp_ev = next((e for e in reversed(events)
                      if e.get("event") == "critpath"), None)
        if cp_ev:
            cp_html = _critpath_html(cp_ev)
            if cp_html:
                rows_html.append(
                    f"<tr class=cp><td colspan=7>{cp_html}</td></tr>")
        # span waterfall (the 'spans' event job_done embeds when tracing
        # was on): one bar per span, offset/width proportional to the
        # job's trace window, lane color by category
        sp_ev = next((e for e in events if e.get("event") == "spans"), None)
        if sp_ev and sp_ev.get("spans"):
            rows_html.append(
                f"<tr class=wf><td colspan=7>"
                f"{_waterfall_html(sp_ev, cp_ev)}</td></tr>")

    refresh = '<meta http-equiv="refresh" content="2">' if live else ""
    doc = f"""<!doctype html><meta charset="utf-8">
{refresh}
<title>tuplex_tpu history</title>
<style>
 body {{ font: 14px system-ui, sans-serif; margin: 2rem; color: #1a1a1a; }}
 table {{ border-collapse: collapse; width: 100%; }}
 th, td {{ text-align: left; padding: .4rem .7rem;
           border-bottom: 1px solid #ddd; }}
 th {{ background: #f5f5f5; }}
 tr.exc td {{ color: #a33; font-size: 12px; border-bottom: none; }}
 tr.task td {{ color: #567; font-size: 12px; border-bottom: none; }}
 tr.running td {{ color: #0a6; font-style: italic; }}
 tr.lint td {{ color: #865; font-size: 12px; border-bottom: none; }}
 tr.wf td {{ border-bottom: none; }}
 tr.dev td {{ border-bottom: none; }}
 tr.dev summary {{ font-size: 12px; color: #456; cursor: pointer; }}
 tr.excp td {{ border-bottom: none; }}
 .excplane summary {{ font-size: 12px; color: #456; cursor: pointer; }}
 table.exctab {{ width: auto; font-size: 12px; margin: .3rem 0 .3rem 1rem; }}
 table.exctab th, table.exctab td {{ padding: .15rem .6rem; }}
 table.exctab td.unexp {{ color: #a33; font-weight: bold; }}
 .driftbar {{ display: inline-block; width: 80px; height: 8px;
              background: #eee; vertical-align: middle; }}
 .driftfill {{ display: block; height: 8px; background: #c2703a; }}
 .respbadge {{ background: #a33; color: #fff; font-size: 11px;
               padding: 0 .4em; border-radius: 3px; }}
 tr.respec td {{ color: #375; font-size: 12px; background: #f4faf4; }}
 .excsample {{ color: #765; font-size: 11px; margin-left: 1rem;
               overflow: hidden; white-space: nowrap;
               text-overflow: ellipsis; }}
 table.devtab {{ width: auto; font-size: 12px; margin: .3rem 0 .3rem 1rem; }}
 table.devtab th, table.devtab td {{ padding: .15rem .6rem; }}
 .rlbar {{ display: inline-block; width: 80px; height: 8px;
           background: #eee; vertical-align: middle; }}
 .rlfill {{ display: block; height: 8px; background: #5a9e6f; }}
 code {{ background: #f0f0f0; padding: 0 .3em; }}
 .waterfall summary {{ font-size: 12px; color: #456; cursor: pointer; }}
 .wfrow {{ display: flex; align-items: center; font-size: 11px;
           line-height: 1.4; }}
 .wflabel {{ flex: 0 0 260px; overflow: hidden; white-space: nowrap;
             text-overflow: ellipsis; color: #345; }}
 .wftrack {{ flex: 1; position: relative; height: 10px;
             background: #f4f4f4; }}
 .wfbar {{ position: absolute; top: 1px; height: 8px; min-width: 1px;
           background: #8ab; }}
 .wfbar.cat-plan {{ background: #7b6bd6; }}
 .wfbar.cat-compile {{ background: #d6906b; }}
 .wfbar.cat-exec {{ background: #5a9e6f; }}
 .wfbar.cat-xfer {{ background: #4a90c2; }}
 .wfbar.cat-mem {{ background: #c25a8a; }}
 .wfbar.cat-job {{ background: #778; }}
 .wfbar.onpath {{ outline: 2px solid #c23a3a; outline-offset: 1px; }}
 tr.cp td {{ border-bottom: none; }}
 .critpath summary {{ font-size: 12px; color: #456; cursor: pointer; }}
 .slowbadge {{ background: #c23a3a; color: #fff; font-size: 11px;
               padding: 0 .4em; border-radius: 3px; }}
 .degbadge {{ background: #b90; color: #fff; font-size: 11px;
              padding: 0 .4em; border-radius: 3px; }}
 .cptrack {{ position: relative; height: 14px; background: #f4f4f4;
             margin: .3rem 0 .3rem 1rem; }}
 .cpseg {{ position: absolute; top: 1px; height: 12px; min-width: 1px;
           background: #8ab; }}
 .cp-admission_wait, .cp-queue_wait {{ background: #aab; }}
 .cp-compile_trace, .cp-compile_lower, .cp-compile_xla
   {{ background: #d6906b; }}
 .cp-h2d, .cp-d2h {{ background: #4a90c2; }}
 .cp-device {{ background: #5a9e6f; }}
 .cp-resolve_general {{ background: #c2a23a; }}
 .cp-resolve_interpreter {{ background: #c2703a; }}
 .cp-merge {{ background: #7b6bd6; }}
 .cp-scheduler_other {{ background: #99a; }}
 .cp-unattributed {{ background: repeating-linear-gradient(45deg, #ddd,
                     #ddd 3px, #bbb 3px, #bbb 6px); }}
 table.cptab {{ width: auto; font-size: 12px; margin: .3rem 0 .3rem 1rem; }}
 table.cptab th, table.cptab td {{ padding: .15rem .6rem; }}
 table.cptab tr.cpdom td {{ font-weight: bold; }}
 table.cptab tr.cpblame td {{ color: #c23a3a; font-weight: bold; }}
</style>
<h1>tuplex_tpu job history</h1>
<p>{len(jobs)} job(s) · {html.escape(src)}</p>
<table>
<tr><th>job</th><th>stages</th><th>rows out</th><th>wall s</th>
<th>fast-path s</th><th>slow-path s</th><th>exceptions</th></tr>
{''.join(rows_html)}
</table>"""
    return doc


def history_to_chrome(log_dir: str = ".", out_path: str =
                      "tuplex_trace.json") -> str:
    """Replay the history file as one Chrome trace-event JSON: each job
    becomes a pid lane (normalized to its own start), using the embedded
    span slices (`spans` events, written when ``tuplex.tpu.trace`` was on)
    and falling back to coarse stage bars synthesized from the job/stage
    event wall-clock timestamps when a job ran without tracing."""
    jobs = _load_jobs(log_dir)

    trace_events: list = []
    for lane, (job_id, events) in enumerate(jobs.items(), start=1):
        # serve-submitted jobs carry a tenant on their rows (serve_job_
        # event / serve_job_spans): label the lane with it so a
        # multi-tenant replay separates by eye
        tenant = next((e["tenant"] for e in events if e.get("tenant")),
                      None)
        lane_name = f"job {job_id}" + (f" ({tenant})" if tenant else "")
        trace_events.append({"name": "process_name", "ph": "M", "pid": lane,
                             "tid": 0, "args": {"name": lane_name}})
        sp_ev = next((e for e in events if e.get("event") == "spans"), None)
        if sp_ev and sp_ev.get("spans"):
            t0 = min(s["ts"] for s in sp_ev["spans"])
            for s in sp_ev["spans"]:
                ev = {"name": s["name"], "cat": s.get("cat") or "exec",
                      "ph": "X", "ts": round(s["ts"] - t0, 1),
                      "dur": round(s.get("dur") or 0.0, 1),
                      "pid": lane, "tid": s.get("tid", 0)}
                if s.get("args"):
                    ev["args"] = s["args"]
                trace_events.append(ev)
            continue
        # no spans recorded: coarse bars off the event wall clocks
        start = next((e for e in events if e.get("event") == "job_start"),
                     None)
        done = next((e for e in events if e.get("event") == "job_done"),
                    None)
        if start is None:
            continue
        t0 = float(start["ts"])
        if done is not None:
            trace_events.append({
                "name": f"job:{start.get('action', '?')}", "cat": "job",
                "ph": "X", "ts": 0.0,
                "dur": round((float(done["ts"]) - t0) * 1e6, 1),
                "pid": lane, "tid": 0,
                "args": {"rows": done.get("rows"),
                         "wall_s": done.get("wall_s")}})
        starts = [e for e in events if e.get("event") == "stage_start"]
        for st in events:
            if st.get("event") != "stage":
                continue
            s0 = next((s for s in starts if s.get("no") == st.get("no")),
                      None)
            ts0 = float(s0["ts"]) if s0 is not None else float(st["ts"])
            trace_events.append({
                "name": f"stage{st.get('no', '?')}:"
                        f"{st.get('kind', '?')}",
                "cat": "exec", "ph": "X",
                "ts": round((ts0 - t0) * 1e6, 1),
                "dur": round((float(st["ts"]) - ts0) * 1e6, 1),
                "pid": lane, "tid": 0,
                "args": {k: v for k, v in
                         (st.get("metrics") or {}).items()
                         if isinstance(v, (int, float))}})
    # multihost: merge per-host span streams (tuplex_trace_host<idx>.jsonl,
    # dumped by every process at job end) into the same timeline. Each
    # stream's events carry their host index as pid (tracing.set_host) —
    # offset into a disjoint range so host lanes never collide with the
    # job lanes numbered 1..N above. Host streams keep their own clock
    # epoch (exact within a host; see runtime/tracing docstring).
    import glob as _glob

    from ..runtime.tracing import load_jsonl as _load_jsonl

    _HOST_LANE_BASE = 1000
    for hp in sorted(_glob.glob(os.path.join(log_dir or ".",
                                             "tuplex_trace_host*.jsonl"))):
        try:
            stream = _load_jsonl(hp)
        except OSError:
            continue
        for ev in stream:
            try:
                ev["pid"] = _HOST_LANE_BASE + int(ev.get("pid", 0))
            except (TypeError, ValueError):
                ev["pid"] = _HOST_LANE_BASE
        trace_events.extend(stream)
    obj = {"traceEvents": trace_events, "displayTimeUnit": "ms"}
    with open(out_path, "w") as fp:
        json.dump(obj, fp)
    return out_path


def _make_server(log_dir: str, port: int, host: str):
    import http.server

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802
            body = _render_doc(log_dir, live=True).encode("utf-8")
            self.send_response(200)
            self.send_header("Content-Type", "text/html; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):  # quiet
            pass

    return http.server.HTTPServer((host, port), Handler)


def serve(log_dir: str = ".", port: int = 5000,
          host: str = "127.0.0.1"):
    """Serve ONLY the rendered dashboard via stdlib http.server (blocking).

    Binds loopback by default and never exposes the filesystem — every GET
    re-renders and returns the dashboard document (auto-refreshing, so an
    open browser tab shows live job progress — the reference's Flask/
    SocketIO/Mongo webui collapsed to the stdlib)."""
    with _make_server(log_dir, port, host) as srv:
        srv.serve_forever()


def start_server(log_dir: str = ".", port: int = 5000,
                 host: str = "127.0.0.1"):
    """Background-thread variant (reference: ensure_webui autostart).
    Returns (server, url); call server.shutdown() to stop. port=0 picks a
    free port."""
    import threading

    srv = _make_server(log_dir, port, host)
    t = threading.Thread(target=srv.serve_forever, daemon=True,
                         name="tuplex-history-server")
    t.start()
    return srv, f"http://{host}:{srv.server_address[1]}/"
