"""Auxiliary subsystems: spill, ORC, history recorder, signals
(reference: LargerThanMemoryDataSet.cc, CacheTest.cc, SignalTest.cc,
test/io ORC round trips, webui tests)."""

import json
import os

import pytest


def test_orc_roundtrip(ctx, tmp_path):
    pytest.importorskip("pyarrow.orc")
    p = str(tmp_path / "t.orc")
    data = [(1, "a", 2.5), (2, "b", None), (3, "c", 4.5)]
    ctx.parallelize(data, columns=["i", "s", "f"]).toorc(p)
    ds = ctx.orc(p)
    assert ds.columns == ["i", "s", "f"]
    assert ds.collect() == data
    assert ds.map(lambda x: x["i"] * 2).collect() == [2, 4, 6]


def test_spill_larger_than_memory(tmp_path):
    import tuplex_tpu

    c = tuplex_tpu.Context({
        "tuplex.executorMemory": "200KB",
        "tuplex.partitionSize": "64KB",
        "tuplex.scratchDir": str(tmp_path),
    })
    data = list(range(100000))
    res = c.parallelize(data).map(lambda x: x + 1).collect()
    assert res == [x + 1 for x in data]
    mm = c.backend.mm
    assert mm.swap_out_count > 0, "expected partitions to spill"
    # each output was boxed at its collect, before it could spill: nothing
    # is read back to be boxed
    assert mm.swap_in_count == 0
    # a sink reads the columns themselves: what spilled comes back
    c.parallelize(data).map(lambda x: x + 1).tocsv(str(tmp_path / "o.csv"))
    assert mm.swap_in_count > 0


def test_history_recorder(tmp_path):
    import tuplex_tpu
    from tuplex_tpu.history import render_report

    c = tuplex_tpu.Context({"tuplex.webui.enable": True,
                            "tuplex.logDir": str(tmp_path)})
    ds = c.parallelize([1, 0, 2]).map(lambda x: 10 // x)
    ds.collect()
    hist = tmp_path / "tuplex_history.jsonl"
    recs = [json.loads(l) for l in hist.read_text().splitlines()]
    events = [r["event"] for r in recs]
    assert "job_start" in events and "stage" in events and "job_done" in events
    done = [r for r in recs if r["event"] == "job_done"][-1]
    assert done["exception_counts"] == {"ZeroDivisionError": 1}
    out = render_report(str(tmp_path))
    assert os.path.exists(out)
    assert "tuplex_tpu job history" in open(out).read()


def test_sigint_between_partitions(tmp_path):
    import tuplex_tpu
    from tuplex_tpu.utils import signals

    c = tuplex_tpu.Context({"tuplex.partitionSize": "4KB"})
    ds = c.parallelize(list(range(20000))).map(lambda x: x * 2)
    # simulate SIGINT arriving mid-job
    orig = signals.check_interrupted
    calls = {"n": 0}

    def fake_check():
        calls["n"] += 1
        if calls["n"] == 3:
            signals._state.requested = True
        orig()

    signals_check = signals.check_interrupted
    try:
        signals.check_interrupted = fake_check
        import tuplex_tpu.exec.local as XL

        with pytest.raises(KeyboardInterrupt):
            ds.collect()
    finally:
        signals.check_interrupted = signals_check


def test_spill_through_aggregate_and_join(tmp_path):
    # review regression: agg/join executors must swap spilled partitions in
    import tuplex_tpu

    c = tuplex_tpu.Context({
        "tuplex.executorMemory": "64KB",
        "tuplex.partitionSize": "32KB",
        "tuplex.scratchDir": str(tmp_path),
    })
    data = [(i % 7, i) for i in range(30000)]
    ds = c.parallelize(data, columns=["k", "v"]).aggregateByKey(
        lambda a, b: a + b, lambda a, r: a + r["v"], 0, ["k"])
    got = dict(ds.collect())
    want: dict = {}
    for k, v in data:
        want[k] = want.get(k, 0) + v
    assert got == want

    left = c.parallelize(data[:5000], columns=["k", "v"])
    right = c.parallelize([(i, f"r{i}") for i in range(7)],
                          columns=["k", "name"])
    joined = left.join(right, "k", "k").collect()
    assert len(joined) == 5000


def test_per_stage_swap_metrics_are_deltas(tmp_path):
    import tuplex_tpu

    c = tuplex_tpu.Context({"tuplex.executorMemory": "64KB",
                            "tuplex.partitionSize": "32KB",
                            "tuplex.scratchDir": str(tmp_path)})
    c.parallelize(list(range(50000))).map(lambda x: x + 1).collect()
    c.parallelize([1, 2, 3]).map(lambda x: x).collect()
    last = [m for m in c.metrics.stages if "swap_out" in m][-1]
    # the tiny second job must not inherit the first job's counters
    assert last["swap_out"] <= 2


def test_repl_detection_and_traceback_cleanup():
    from tuplex_tpu.utils import repl

    # non-interactive test runner: every detector is False
    assert repl.in_google_colab() is False
    assert repl.in_jupyter_notebook() is False
    assert repl.in_interactive_shell() is False

    def user_udf(x):
        return 1 // x

    try:
        user_udf(0)
    except ZeroDivisionError as e:
        txt = repl.clean_udf_traceback(e)
    assert "user_udf" in txt and "ZeroDivisionError" in txt
    assert "tuplex_tpu/utils/repl.py" not in txt


# ---------------------------------------------------------------------------
# plan visualization + codegen stats (reference: Context.cc:171
# visualizeOperationGraph; InstructionCountPass.h)
# ---------------------------------------------------------------------------

def test_explain_and_dot(ctx, capsys):
    ds = (ctx.parallelize([1, 2, 3, 4])
          .map(lambda x: x * 2)
          .filter(lambda x: x > 2))
    text = ds.explain()
    assert "Stage 0" in text and "Map" in text and "Filter" in text
    dot = ds.to_dot()
    assert dot.startswith("digraph plan {") and "Map" in dot
    assert dot.count("->") >= 2


def test_explain_code_stats(tmp_path):
    import tuplex_tpu

    ctx = tuplex_tpu.Context({"tuplex.optimizer.codeStats": "true"})
    ds = ctx.parallelize([1, 2, 3, 4]).map(lambda x: x + 1)
    text = ds.explain()
    assert "jaxpr equations" in text


def test_jedi_completer():
    from tuplex_tpu.utils.repl import JediCompleter

    jc = JediCompleter(lambda: {"alpha_beta": 1, "alpha_gamma": 2})
    names = jc._complete_line("alpha_")
    assert "alpha_beta" in names and "alpha_gamma" in names


def test_jedi_completer_dotted(monkeypatch):
    """readline passes only the word under the cursor ('.' is a delimiter);
    candidates must complete that word, not the whole expression."""
    import sys
    import types

    from tuplex_tpu.utils import repl

    class Obj:
        def csv(self):
            pass

    jc = repl.JediCompleter(lambda: {"c": Obj()})
    fake = types.SimpleNamespace(get_line_buffer=lambda: "c.cs",
                                 get_endidx=lambda: 4)
    monkeypatch.setitem(sys.modules, "readline", fake)
    assert jc.complete("cs", 0) == "csv"


def test_stdlib_completer_fallback():
    from tuplex_tpu.utils.repl import JediCompleter

    jc = JediCompleter(lambda: {"alpha_beta": 1})
    # token-level fallback must work inside call contexts (readline hands
    # us 'alp' for 'len(alp')
    assert "alpha_beta" in jc._stdlib_complete("alp")


def test_sample_exception_previews_recorded(tmp_path):
    # reference: SampleProcessor runs sample rows through real UDFs so the
    # webui can preview exceptions BEFORE execution; our plan-time tracing
    # records the same per-operator previews into the job_start event
    import json

    import tuplex_tpu

    c = tuplex_tpu.Context({"tuplex.webui.enable": True,
                            "tuplex.logDir": str(tmp_path)})
    got = (c.parallelize([1, 2, 0, 4])
           .map(lambda x: 12 // x)
           .resolve(ZeroDivisionError, lambda x: -1)
           .collect())
    assert got == [12, 6, -1, 3]
    events = [json.loads(ln) for ln in
              open(tmp_path / "tuplex_history.jsonl")]
    starts = [e for e in events if e["event"] == "job_start"]
    pv = [p for e in starts for p in e.get("sample_exception_previews", [])]
    assert any(p["exc"] == "ZeroDivisionError" for p in pv), pv


def test_sample_previews_dedup_mapcolumn_and_memo(tmp_path):
    import json

    import tuplex_tpu

    # mapColumn failures preview too, entries dedup, and a rebuilt
    # identical pipeline (cross-job memo hit) still carries previews
    def run(logdir):
        c = tuplex_tpu.Context({"tuplex.webui.enable": True,
                                "tuplex.logDir": str(logdir)})
        got = (c.parallelize([{"a": 1}, {"a": 0}, {"a": 0}])
               .mapColumn("a", lambda v: 10 // v)
               .resolve(ZeroDivisionError, lambda v: -1)
               .collect())
        assert got == [10, -1, -1]
        events = [json.loads(ln) for ln in
                  open(logdir / "tuplex_history.jsonl")]
        return [p for e in events if e["event"] == "job_start"
                for p in e.get("sample_exception_previews", [])]

    pv = run(tmp_path)
    assert any(p["exc"] == "ZeroDivisionError" and
               p["op"] == "MapColumnOperator" for p in pv), pv
    # duplicates collapse: both zero rows produce identical entries -> one
    assert len([p for p in pv if p["exc"] == "ZeroDivisionError"]) == 1


def test_profile_dir_writes_trace(tmp_path):
    import os

    import tuplex_tpu

    c = tuplex_tpu.Context({"tuplex.tpu.profileDir": str(tmp_path / "prof")})
    got = c.parallelize([1, 2, 3]).map(lambda x: x * 2).collect()
    assert got == [2, 4, 6]
    # a plugins/profile dir with at least one trace artifact appears
    found = [os.path.join(r, f) for r, _, fs in os.walk(tmp_path / "prof")
             for f in fs]
    assert found, "no profiler artifacts written"


def test_backend_specific_metrics_survive(tmp_path):
    import tuplex_tpu

    c = tuplex_tpu.Context({"tuplex.backend": "serverless",
                            "tuplex.aws.scratchDir": str(tmp_path),
                            "tuplex.aws.maxConcurrency": 2})
    c.parallelize(list(range(2000))).map(lambda x: x + 1).collect()
    stages = c.metrics.as_dict()["stages"]
    assert any("serverless_tasks" in s for s in stages), stages


def test_history_live_events(tmp_path):
    """VERDICT r3 #9: stage_start/progress records appear DURING the job and
    the dashboard renders an in-flight job as RUNNING before job_done."""
    import json

    import tuplex_tpu
    from tuplex_tpu.history.recorder import _render_doc

    c = tuplex_tpu.Context({"tuplex.webui.enable": True,
                            "tuplex.logDir": str(tmp_path),
                            "tuplex.partitionSize": "16KB"})
    c.parallelize(list(range(4000))).map(lambda x: x + 1).collect()

    events = [json.loads(ln) for ln in
              open(tmp_path / "tuplex_history.jsonl")]
    kinds = [e["event"] for e in events]
    assert "stage_start" in kinds
    assert kinds.index("stage_start") < kinds.index("stage")
    assert "progress" in kinds, kinds
    prog = next(e for e in events if e["event"] == "progress")
    assert prog["rows"] > 0 and prog["parts"] >= 1

    # replay only the records up to the first progress event: the dashboard
    # must show the job as RUNNING (this is what a live poll mid-job sees)
    cut = kinds.index("progress") + 1
    live_dir = tmp_path / "live"
    live_dir.mkdir()
    with open(live_dir / "tuplex_history.jsonl", "w") as fp:
        for e in events[:cut]:
            fp.write(json.dumps(e) + "\n")
    doc = _render_doc(str(live_dir), live=True)
    assert "RUNNING" in doc and "stage 1" in doc
