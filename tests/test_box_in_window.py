"""A `collect()` or `take(n)` whose last stage is a transform stage boxes
each output partition at its collect, inside the dispatch window
(`Partition.boxed`); the action then only concatenates. The answer is the
one that boxing after the plan gives, the rows of a restarted tier come
once, and the other last stages, the sinks and the serverless backend box
as before (or not at all)."""

import pytest

import tuplex_tpu
from tuplex_tpu.exec import compilequeue as CQ
from tuplex_tpu.runtime import columns as C
from tuplex_tpu.runtime import tracing as TR


@pytest.fixture()
def spans():
    was = TR.enabled()
    TR.enable(True)
    TR.clear()
    yield lambda *names: [e for e in TR.events_since(0)
                          if e["name"] in names and e.get("dur") is not None]
    TR.enable(was)


@pytest.fixture()
def ctx():
    c = tuplex_tpu.Context({"tuplex.partitionSize": "1KB",
                            "tuplex.sample.maxDetectionRows": "64"})
    yield c
    c.close()


# module-level UDFs: reflection needs real source files
def twice_or_str(x):
    return x * 2 if x % 50 else str(x)


def hundred_over(x):
    return 100 // (x % 7)


def plus_one(x):
    return x + 1


def opt_str(x):
    return (x[0], None if x[0] % 5 == 0 else x[1] + "!")


def by_plan(ds, limit):
    """What the action returned before: the last stage's partitions boxed
    one after the other once the plan has run, cut at `limit`."""
    parts = ds._execute_partitions(limit)
    rows = [r for p in parts for r in C.box_rows(p)[0]]
    return rows[:limit] if limit >= 0 else rows


CASES = {
    # the interpreter's str rows spliced among the native decode
    "fallback-splice": (lambda c: c.parallelize(list(range(300)))
                        .map(twice_or_str), -1,
                        [twice_or_str(x) for x in range(300)]),
    # a resolver's rows merged back in order
    "resolved": (lambda c: c.parallelize(list(range(300))).map(hundred_over)
                 .resolve(ZeroDivisionError, lambda x: -1), -1,
                 [hundred_over(x) if x % 7 else -1 for x in range(300)]),
    # one column: bare values, not 1-tuples
    "single-column": (lambda c: c.parallelize(list(range(500)))
                      .map(plus_one), -1, [x + 1 for x in range(500)]),
    # Option and str columns
    "option-str": (lambda c: c.parallelize([(i, f"s{i}") for i in range(400)])
                   .map(opt_str), -1,
                   [opt_str((i, f"s{i}")) for i in range(400)]),
    # take(n) cut inside a partition
    "take-inside": (lambda c: c.parallelize(list(range(500))).map(plus_one),
                    157, [x + 1 for x in range(157)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_answer_is_the_one_boxing_after_the_plan_gives(ctx, spans,
                                                           case):
    build, limit, want = CASES[case]
    got = build(ctx).collect() if limit < 0 else build(ctx).take(limit)
    assert got == want
    assert [type(v) for v in got] == [type(v) for v in want]
    (rec,) = [m for m in ctx.metrics.stages if "partitions_boxed" in m]
    boxes = spans("collect:box-partition")
    assert boxes and rec["partitions_boxed"] == len(boxes)
    assert {b["args"]["in_window"] for b in boxes} == {1}
    # a take boxes no row it drops
    assert sum(b["args"]["rows"] for b in boxes) == len(want)
    assert by_plan(build(ctx), limit) == got


def test_every_in_window_box_sits_under_the_stage_on_the_job_thread(
        ctx, spans):
    ctx.parallelize(list(range(300))).map(twice_or_str).collect()
    ctx.parallelize(list(range(500))).map(plus_one).take(157)
    jobs = {j["id"]: j for j in spans("job")}
    stages = {s["id"]: s for s in spans("stage:execute")}
    boxes = spans("collect:box-partition")
    assert len(jobs) == 2 and boxes
    for b in boxes:
        st = stages[b["parent"]]
        assert b["tid"] == st["tid"] == jobs[b["job"]]["tid"]
        assert st["ts"] <= b["ts"] and \
            b["ts"] + b["dur"] <= st["ts"] + st["dur"] + 1e-6


def test_a_restarted_tier_yields_each_row_once(monkeypatch, spans):
    """The last partition's compile blows the deadline after the partitions
    before it were boxed on the compiled tier: the stage restarts from
    partition 0, and only the restarted tier's rows reach the answer."""
    from tuplex_tpu.exec import local as LB

    real = LB.LocalBackend._dispatch_partition
    injected = []

    def dispatch(self, part, *a, **kw):
        if part.start_index == 4096 and not injected:
            injected.append(part.start_index)
            return (part, LB._CompileTimedOut(CQ.CompileTimeout("injected")),
                    0.0, None)
        return real(self, part, *a, **kw)

    monkeypatch.setattr(LB.LocalBackend, "_dispatch_partition", dispatch)
    # a deadline makes the stage keep its input for a replay
    c = tuplex_tpu.Context({"tuplex.partitionSize": "8KB",
                            "tuplex.tpu.compileDeadlineS": 600.0})
    try:
        got = c.parallelize(list(range(5000))).map(plus_one).collect()
        s = c.metrics.stages[-1]
    finally:
        c.close()
    assert injected and got == [x + 1 for x in range(5000)]
    assert s["tier_restarts"] == 1 and s["tier"] == "interpreter", s
    boxes = spans("collect:box-partition")
    # the aborted tier boxed the four partitions before the last: their
    # rows went with it
    assert [b["args"]["rows"] for b in boxes] == [1024] * 4 \
        + [1024] * 4 + [904]
    assert s["partitions_boxed"] == 5
    assert all(b["args"]["in_window"] == 1 for b in boxes)


def agg_sum(a, b):
    return a + b


def agg_add(a, x):
    return a + x


def test_other_last_stages_and_the_sinks_box_as_before(ctx, spans,
                                                       tmp_path):
    # an aggregate: its rows are boxed by the action, after the plan
    got = ctx.parallelize(list(range(300))).map(plus_one) \
        .aggregate(agg_sum, agg_add, 0).collect()
    assert got == [sum(range(1, 301))]
    boxes = spans("collect:box-partition")
    assert boxes and {b["args"]["in_window"] for b in boxes} == {0}
    (outer,) = spans("collect:box-rows")
    assert all(b["parent"] == outer["id"] for b in boxes)
    assert all(m.get("partitions_boxed", 0) == 0
               for m in ctx.metrics.stages)
    # a join: the same
    TR.clear()
    left = ctx.parallelize([(i, i % 4) for i in range(200)],
                           columns=["a", "k"])
    right = ctx.parallelize([(k, f"n{k}") for k in range(4)],
                            columns=["k", "name"])
    got = left.join(right, "k", "k").collect()
    assert sorted(got) == sorted((i, i % 4, f"n{i % 4}")
                                 for i in range(200))
    boxes = spans("collect:box-partition")
    assert boxes and {b["args"]["in_window"] for b in boxes} == {0}
    # a sink streams columnar partitions: nothing is boxed
    TR.clear()
    ctx.parallelize(list(range(300))).map(plus_one).tocsv(
        str(tmp_path / "out.csv"))
    assert not spans("collect:box-partition")
    assert ctx.metrics.stages[-1]["partitions_boxed"] == 0


def test_a_backend_that_cannot_box_is_not_asked(ctx, spans, monkeypatch):
    asked = []
    real = type(ctx.backend).execute_any

    def execute_any(self, *a, **kw):
        asked.append(kw.get("box_output", False))
        return real(self, *a, **kw)

    monkeypatch.setattr(type(ctx.backend), "execute_any", execute_any)
    monkeypatch.setattr(type(ctx.backend), "supports_box_output", False)
    got = ctx.parallelize(list(range(300))).map(twice_or_str).collect()
    assert got == [twice_or_str(x) for x in range(300)]
    assert asked == [False]
    boxes = spans("collect:box-partition")
    assert boxes and {b["args"]["in_window"] for b in boxes} == {0}
    from tuplex_tpu.exec.serverless import ServerlessBackend

    assert ServerlessBackend.supports_box_output is False
