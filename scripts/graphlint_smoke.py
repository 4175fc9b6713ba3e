#!/usr/bin/env python3
"""Graphlint zero-false-positive gate: plan (never execute) every bundled
model pipeline and check the static jaxpr vetting verdicts against the
calibrated corpus (compiler/graphlint module docstring):

  * no CLEAN stage anywhere carries a wedge-severity finding — a false
    positive here silently degrades a healthy stage to the interpreter;
  * the flights airport table, cleaned with every column live, is
    pre-degraded by EXACTLY the pinned rule ``wide-str-compaction``
    (ROADMAP residue c; as a join's build side its two capwords operators
    are dead since PR 35 and the shape no longer plans);
  * re-analysis of the planned flights stages finds exactly one more
    carrier of the rule — the probe-side mega-segment whose production
    compile blows even a 300 s XLA:CPU deadline (the compile plane vets
    it at submission; tests/test_models.py proves zero kills end-to-end).

Plan-only: nothing compiles, nothing collects, so the gate runs in
tens of seconds. CI wires it as a tier-1 test via tests/test_graphlint.py:

    JAX_PLATFORMS=cpu python scripts/graphlint_smoke.py

Exits 0 and prints one `graphlint-smoke OK ...` line on success."""

from __future__ import annotations

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))          # run from anywhere

PINNED_RULE = "wide-str-compaction"


def _planned_stages(ctx, sink, tag):
    """(label, stage) for the top-level plan AND the lazily-planned join
    build sides (the airport wedge lives on one)."""
    from tuplex_tpu.plan.physical import (JoinStage, TransformStage,
                                          plan_stages)

    out = []
    stages = plan_stages(sink._op, ctx.options_store)
    for i, st in enumerate(stages):
        if isinstance(st, TransformStage):
            out.append((f"{tag}[{i}]", st))
        elif isinstance(st, JoinStage):
            for j, bs in enumerate(plan_stages(st.op.right,
                                               ctx.options_store)):
                if isinstance(bs, TransformStage):
                    out.append((f"{tag}[{i}].build[{j}]", bs))
    return out


def main() -> int:
    import string

    import tuplex_tpu
    from tuplex_tpu.compiler import graphlint as GL
    from tuplex_tpu.models import flights, logs, nyc311, tpch, zillow

    assert GL.enabled(), \
        "graphlint disabled (TUPLEX_GRAPHLINT=0 set?) — nothing to smoke"

    tmp = tempfile.mkdtemp(prefix="graphlint_smoke_")
    ctx = tuplex_tpu.Context({"tuplex.partitionSize": "256KB",
                              "tuplex.sample.maxDetectionRows": "64",
                              "tuplex.scratchDir": os.path.join(tmp, "s")})

    labelled = []
    zp = os.path.join(tmp, "z.csv")
    zillow.generate_csv(zp, 300, seed=4)
    labelled += _planned_stages(ctx, zillow.build_pipeline(ctx.csv(zp)),
                                "zillow")
    perf, car, air = (os.path.join(tmp, n)
                      for n in ("f.csv", "c.csv", "a.txt"))
    flights.generate_perf_csv(perf, 300, seed=2)
    flights.generate_carrier_csv(car)
    flights.generate_airport_db(air)
    labelled += _planned_stages(
        ctx, flights.build_pipeline(ctx, perf, car, air), "flights")
    # the airport table cleaned as a data set of its own, every column
    # live: under the flights joins its two capwords operators are dead
    # and dropped with their columns (projection through joins, PR 35),
    # so the wedging shape no longer plans there
    airports = ctx.csv(air, columns=flights.AIRPORT_COLS, delimiter=":",
                       header=False, null_values=["", "N/a", "N/A"])
    airports = airports.mapColumn(
        "AirportName", lambda x: string.capwords(x) if x else None)
    airports = airports.mapColumn(
        "AirportCity", lambda x: string.capwords(x) if x else None)
    labelled += _planned_stages(ctx, airports, "flights_airports")
    tp = os.path.join(tmp, "li.csv")
    tpch.generate_csv(tp, 500, seed=4)
    labelled += _planned_stages(ctx, tpch.q6(ctx.csv(tp)), "tpch_q6")
    labelled += _planned_stages(ctx, tpch.q1(ctx.csv(tp)), "tpch_q1")
    np_ = os.path.join(tmp, "nyc.csv")
    nyc311.generate_csv(np_, 300, seed=3)
    labelled += _planned_stages(ctx, nyc311.build_pipeline(ctx, np_),
                                "nyc311")
    lg = os.path.join(tmp, "log.txt")
    logs.generate_log(lg, 300, seed=6)
    labelled += _planned_stages(ctx, logs.build_pipeline(ctx.text(lg),
                                                         "strip"),
                                "logs_strip")
    labelled += _planned_stages(ctx, logs.build_pipeline(ctx.text(lg),
                                                         "regex"),
                                "logs_regex")

    # 1) plan-time verdicts: a wedge finding is allowed ONLY on a stage
    #    the planner pre-degraded with the pinned rule
    pre_degraded = []
    for label, st in labelled:
        rule = getattr(st, "hazard_rule", None)
        rep = getattr(st, "graph_report", None)
        wedges = {f.rule for f in rep.findings
                  if f.severity == "wedge"} if rep is not None else set()
        if rule is not None:
            assert rule == PINNED_RULE, \
                f"{label}: unexpected pre-degrade rule {rule!r}"
            assert wedges == {PINNED_RULE}, \
                (f"{label}: pre-degraded stage must report exactly the "
                 f"pinned rule, got {sorted(wedges)}")
            pre_degraded.append(label)
        else:
            assert not wedges, \
                f"{label}: FALSE POSITIVE wedge finding(s) {sorted(wedges)}"
    assert pre_degraded and all(lbl.startswith("flights")
                                for lbl in pre_degraded), \
        (f"expected the flights airport table (and only it) "
         f"pre-degraded at plan time, got {pre_degraded}")

    # 2) submission-plane preview: re-analyze every planned stage the
    #    compile plane would actually submit — the rule must fire on
    #    exactly one more stage, the flights probe-side mega-segment
    resubmit_wedges = []
    for label, st in labelled:
        if getattr(st, "force_interpret", False):
            continue
        rep = GL.analyze_stage(st, platform="cpu")
        if rep is not None and rep.wedge:
            resubmit_wedges.append((label, rep))
    assert len(resubmit_wedges) == 1, \
        (f"expected exactly the flights probe-side segment at the "
         f"compile plane, got {[lbl for lbl, _ in resubmit_wedges]}")
    lbl, rep = resubmit_wedges[0]
    assert lbl.startswith("flights"), lbl
    assert {f.rule for f in rep.findings
            if f.severity == "wedge"} == {PINNED_RULE}, lbl

    ctx.close()
    print(f"graphlint-smoke OK — {len(labelled)} stage(s) vetted, "
          f"plan-time pre-degrades: {pre_degraded}, "
          f"submission-plane wedge: {lbl} "
          f"(rule {PINNED_RULE}, zero false positives)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
