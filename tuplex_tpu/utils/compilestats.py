"""`python -m tuplex_tpu compilestats <script.py>` — plan-time compile
forecast.

Runs the pipeline script with every DataSet ACTION stubbed out (collect/
take/show/tocsv/... capture the plan and return empty), plans each captured
action, and prints per stage: fused op count, jaxpr equation count, the
predicted compile seconds where the platform has a compile-cost curve
(plan/splittuner.py), and which stages would share one executable under the
content-addressed compile cache (exec/compilequeue.py fingerprints).

Unlike `lint` (purely syntactic, never imports the script), compilestats
MUST import the script to build its operator graph — sources are sniffed
and samples traced, but no stage executes and nothing compiles.
"""

from __future__ import annotations

import sys
from typing import Optional


def _capture_plans(script: str) -> list:
    """Import/run the script with actions stubbed; returns captured
    (action, sink_op, options_store) triples in call order."""
    import runpy

    from ..api.dataset import DataSet

    captured: list = []
    saved = {name: getattr(DataSet, name)
             for name in ("_execute", "_execute_partitions",
                          "tocsv", "toorc", "totuplex")}

    def fake_execute(self, limit: int):
        captured.append(("collect" if limit < 0 else f"take({limit})",
                         self._op, self._context.options_store))
        return []

    def fake_partitions(self, limit: int, output_sink=None):
        captured.append(("write", self._op, self._context.options_store))
        self._t_job = 0.0
        return []

    def fake_sink(self, path, *a, **kw):
        # capture WITHOUT creating an (empty) output file on disk
        captured.append((f"write({path!r})", self._op,
                         self._context.options_store))

    DataSet._execute = fake_execute
    DataSet._execute_partitions = fake_partitions
    DataSet.tocsv = DataSet.toorc = DataSet.totuplex = fake_sink
    try:
        runpy.run_path(script, run_name="__main__")
    finally:
        for name, fn in saved.items():
            setattr(DataSet, name, fn)
    return captured


def _stage_rows(stages, platform: str) -> tuple[list, dict]:
    """Per-stage stat rows + fingerprint groups for one plan."""
    from ..plan.physical import TransformStage, stage_fingerprint
    from ..plan.splittuner import predict
    from .planviz import stage_eqn_count

    rows = []
    by_fp: dict[str, list[int]] = {}
    for i, st in enumerate(stages):
        kind = type(st).__name__
        if not isinstance(st, TransformStage):
            rows.append({"i": i, "kind": kind, "n_ops": None})
            continue
        n_ops = len(st.ops)
        row = {"i": i, "kind": kind, "n_ops": n_ops,
               "key": st.key(),
               "interpreter": bool(st.force_interpret)}
        if not st.force_interpret:
            row["eqns"] = stage_eqn_count(st)
            pred = getattr(st, "predicted_compile_s", None)
            row["predicted_s"] = float(pred) if pred is not None \
                else predict(platform, n_ops)
            fp = stage_fingerprint(st)
            if fp is not None:
                row["fp"] = fp
                by_fp.setdefault(fp, []).append(i)
        dec = getattr(st, "split_decision", None)
        if dec is not None:
            row["split"] = dec.describe()
        rep = getattr(st, "graph_report", None)
        if rep is not None:
            row["hazard_score"] = float(min(rep.hazard_score, 1e9))
            row["findings"] = [f.line() for f in rep.findings]
            row["worst"] = rep.worst_severity()
        if getattr(st, "hazard_rule", None):
            row["hazard_rule"] = st.hazard_rule
        rows.append(row)
    return rows, {fp: ix for fp, ix in by_fp.items() if len(ix) > 1}


def _cost_line(entry: Optional[dict]) -> Optional[str]:
    """One human line from a devprof stage-index entry (runtime/devprof):
    the measured device-plane record a PREVIOUS run of this stage left in
    the AOT cache dir — ``stage.key()`` is content-derived, so planning
    the same script again computes the same key. Explicit about the two
    nothing-to-show cases instead of printing blanks."""
    from ..runtime import devprof

    if entry is None:
        return None     # never ran: the caller prints nothing extra
    ana = entry.get("analysis")
    if ana is None:
        return ("device analysis UNAVAILABLE (backend returned nothing; "
                "measured device "
                f"{entry.get('device_s_per_dispatch', 0.0) * 1e3:.1f} "
                "ms/dispatch)")
    cost = devprof.StageCost.from_dict(ana)
    bits = [devprof.fmt_flops(cost.flops),
            f"{devprof.fmt_bytes(cost.bytes_accessed)} accessed",
            f"peak {devprof.fmt_bytes(cost.peak_bytes)}"]
    ds = entry.get("device_s_per_dispatch")
    if ds:
        bits.append(f"device {ds * 1e3:.1f} ms/dispatch")
    rf = entry.get("roofline_frac")
    if rf:
        bits.append(f"roofline {rf * 100:.1f}%")
    if cost.partial:
        bits.append("(partial analysis)")
    return "measured cost: " + ", ".join(bits)


def lint_jaxprs(script: str, stream=None) -> tuple[int, int]:
    """`lint`'s jaxpr findings section: import the script with actions
    stubbed (same harness as compilestats — no stage executes, nothing
    compiles), plan each action, and print every graphlint finding the
    planner attached while vetting the stages. Returns
    ``(n_findings, n_wedge)`` so `lint --strict` can fail on
    wedge-severity jaxpr findings."""
    import sys as _sys

    from ..plan.physical import TransformStage, plan_stages

    stream = stream if stream is not None else _sys.stdout

    def emit(line=""):
        print(line, file=stream)

    from ..plan.physical import JoinStage

    captured = _capture_plans(script)
    n_findings = n_wedge = 0
    emitted_header = False
    for pi, (action, sink, options) in enumerate(captured):
        try:
            stages = plan_stages(sink, options)
        except Exception as e:
            emit(f"jaxpr findings: planning {action} failed: "
                 f"{type(e).__name__}: {e}")
            continue
        # join build sides plan lazily at execution time; vet them here
        # too (the flights airport wedge lives on one)
        labelled = [(str(i), st) for i, st in enumerate(stages)]
        for i, st in enumerate(stages):
            if isinstance(st, JoinStage):
                try:
                    labelled += [(f"{i}.build[{j}]", bs) for j, bs in
                                 enumerate(plan_stages(st.op.right,
                                                       options))]
                except Exception:
                    pass
        for i, st in labelled:
            if not isinstance(st, TransformStage):
                continue
            rep = getattr(st, "graph_report", None)
            if rep is None or not rep.findings:
                continue
            if not emitted_header:
                emit()
                emit("jaxpr findings (compiler/graphlint, post-trace "
                     "pre-compile):")
                emitted_header = True
            ops = ",".join(type(o).__name__ for o in st.ops)
            emit(f"  plan {pi + 1} ({action}) stage {i} [{ops}] — "
                 f"hazard score {min(rep.hazard_score, 1e9):.1f}s")
            for f in rep.findings:
                emit(f"    {f.line()}")
                n_findings += 1
                if f.severity == "wedge":
                    n_wedge += 1
            if getattr(st, "hazard_rule", None):
                emit(f"    -> pre-degraded to the interpreter "
                     f"(rule {st.hazard_rule})")
    if emitted_header:
        emit()
        emit(f"jaxpr findings: {n_findings} finding(s), "
             f"{n_wedge} wedge-severity")
    return n_findings, n_wedge


def main(script: str, platform: Optional[str] = None) -> int:
    from ..plan import splittuner as ST
    from ..plan.physical import plan_stages
    from ..runtime import devprof
    from ..runtime.jaxcfg import jax

    try:
        captured = _capture_plans(script)
    except SystemExit as e:
        if e.code not in (0, None):
            print(f"compilestats: script exited with {e.code}",
                  file=sys.stderr)
            return 2
        captured = []
    if not captured:
        print("compilestats: the script ran no DataSet action "
              "(collect/take/show/tocsv/...)", file=sys.stderr)
        return 1

    platform = platform or jax.default_backend()
    curve = ST.CURVES.get(platform)
    print(f"compile-cost curve: platform={platform} "
          + (f"exponent {curve[2]:.2f}, boundary cost "
             f"{ST.BOUNDARY_S[platform] * 1e3:.1f} ms" if curve
             else "none (stages stay fused, no compile predicted)"))
    cost_index = devprof.load_stage_index()
    rc = 0
    for pi, (action, sink, options) in enumerate(captured):
        print(f"\nplan {pi + 1} ({action}):")
        try:
            stages = plan_stages(sink, options)
        except Exception as e:
            print(f"  planning failed: {type(e).__name__}: {e}")
            rc = 1
            continue
        rows, dedup = _stage_rows(stages, platform)
        total = 0.0
        for row in rows:
            head = f"  stage {row['i']} [{row['kind']}]"
            if row["n_ops"] is None:
                print(f"{head}: pipeline breaker")
                continue
            bits = [f"{row['n_ops']} ops"]
            if row.get("eqns") is not None:
                bits.append(f"{row['eqns']} jaxpr eqns")
            if row.get("interpreter"):
                bits.append("interpreter segment (no compile)")
            if row.get("predicted_s") is not None \
                    and not row.get("interpreter"):
                bits.append(f"predicted compile {row['predicted_s']:.1f}s")
                total += row["predicted_s"]
            print(f"{head}: {', '.join(bits)}")
            if row.get("split"):
                print(f"    {row['split']}")
            if row.get("hazard_rule"):
                print(f"    HAZARD: pre-degraded to the interpreter "
                      f"(rule {row['hazard_rule']})")
            elif row.get("hazard_score") is not None:
                hline = (f"    hazard score "
                         f"{row['hazard_score']:.1f}s")
                n_find = len(row.get("findings") or ())
                if n_find:
                    hline += f", {n_find} jaxpr finding(s)"
                print(hline)
            if not row.get("interpreter"):
                cl = _cost_line(cost_index.get(row.get("key", "")))
                if cl:
                    print(f"    {cl}")
        saved = 0.0
        by_i = {r["i"]: r for r in rows}
        for fp, ix in dedup.items():
            dupes = ix[1:]
            saved += sum(r["predicted_s"] for r in rows
                         if r["i"] in dupes and r.get("predicted_s"))
            print(f"  dedup: stages {ix} share one executable "
                  f"(fingerprint {fp[:12]}…)")
            # the shared executable's measured device-plane cost (any
            # member's index entry — they dedup to one compile)
            gl = next((cl for i2 in ix
                       if (cl := _cost_line(cost_index.get(
                           by_i.get(i2, {}).get("key", ""))))), None)
            if gl:
                print(f"    group {gl}")
            else:
                print("    group cost: no record yet (stages never ran "
                      "with devprof on)")
        if curve is None:
            continue
        budget = options.get_float("tuplex.tpu.compileBudgetS", 480.0)
        line = (f"  predicted compile total: {total:.1f}s serial"
                + (f", {total - saved:.1f}s after dedup" if saved else ""))
        if budget > 0:
            line += (f"; budget {budget:.0f}s -> "
                     + ("fits" if total - saved <= budget else "OVER"))
        print(line)
    return rc
